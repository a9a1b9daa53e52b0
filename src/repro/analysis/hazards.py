"""AST hot-path hazard linter for the jit-extent modules.

The fused slot step is fast because nothing inside its traced extent
touches the host: no ``np.*`` calls, no ``.item()``/``float()``
concretization, no Python branching on array *contents*, and every
dynamic axis is padded to a documented bucket before it reaches a jitted
entry.  Those rules lived in reviewers' heads; this linter makes them
mechanical.

Hazard taxonomy (rule ids):

==========================  ==============================================
``host-np-call``            ``np.*`` use inside a traced function — host
                            numpy silently syncs and falls off the device
``host-scalar-coerce``      ``.item()``/``.tolist()``/``float()``/
                            ``int()``/``bool()`` on a traced value
``host-print``              ``print`` inside a traced function (use
                            ``jax.debug.print``)
``py-loop-over-array``      Python ``for`` over array contents inside a
                            traced function (loops over ``range``/static
                            shapes are fine — they unroll)
``py-branch-on-array``      ``if``/``while`` testing ``.any()``/``.all()``
                            /``.item()``/``bool(...)`` inside a traced
                            function — a concretization point
``jnp-upload-outside-x64``  device upload (``jnp.asarray`` etc.) outside
                            a lexical ``enable_x64`` block in a module
                            that owns float64-parity math — silently
                            downcasts float64 operands to float32
``retrace-literal-arg``     a bare Python number/bool passed to a jitted
                            entry — weak-typed scalars bake into the
                            trace and retrace per distinct value
``retrace-unbucketed-pad``  a host wrapper pads operands for a jitted
                            entry without routing the dynamic axis
                            through a registered bucket helper
==========================  ==============================================

Traced extent discovery: ``@jax.jit`` / ``@partial(jax.jit, ...)``
decorated functions, kernel bodies passed to ``pl.pallas_call``, the
registry's ``EXTRA_TRACED`` helpers, plus every ``def`` nested inside
any of those.  Everything else in a jit-extent module is host-wrapper
code, where only the retrace/dtype rules apply.
"""
from __future__ import annotations

import ast
import pathlib
from typing import Dict, List, Optional, Sequence, Set

from repro.analysis import registry
from repro.analysis.findings import Finding

_COERCE_BUILTINS = ("float", "int", "bool")
_COERCE_METHODS = ("item", "tolist", "numpy", "block_until_ready")
_UPLOAD_FNS = ("asarray", "array", "zeros", "full", "ones", "arange")
_SAFE_ITER_CALLS = ("range", "enumerate", "zip", "reversed")
# the float64 scope as called: ``jax.enable_x64(True)`` or, after
# ``from jax import enable_x64``, the bare name
_X64_SCOPES = ("enable_x64", "jax.enable_x64")


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` attribute chains as a dotted string (None otherwise)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_decorator(dec: ast.AST) -> bool:
    name = _dotted(dec)
    if name in ("jax.jit", "jax.pmap"):
        return True
    if isinstance(dec, ast.Call):
        fn = _dotted(dec.func)
        if fn in ("partial", "functools.partial") and dec.args:
            inner = _dotted(dec.args[0])
            return inner in ("jax.jit", "jax.pmap", "checkify.checkify",
                            "jax.checkify.checkify")
    return False


def _callable_target(node: ast.AST) -> Optional[str]:
    """The function name a callable expression refers to: a bare Name,
    or the first argument of ``[functools.]partial(F, ...)``."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call):
        fn = _dotted(node.func)
        if fn in ("partial", "functools.partial") and node.args:
            return _callable_target(node.args[0])
    return None


def _static_argnames(node: ast.FunctionDef) -> Set[str]:
    """Names declared static in a ``partial(jax.jit, static_argnames=…)``
    decorator — values safe to coerce to Python scalars at trace time."""
    out: Set[str] = set()
    for dec in node.decorator_list:
        if not (isinstance(dec, ast.Call) and _is_jit_decorator(dec)):
            continue
        for kw in dec.keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        out.add(sub.value)
    return out


class _ModuleInfo(ast.NodeVisitor):
    """First pass: alias maps, traced function names, jitted entry names
    (module-level bindings whose value is jit-compiled)."""

    def __init__(self):
        self.np_aliases: Set[str] = set()
        self.jnp_aliases: Set[str] = set()
        self.uses_x64 = False
        self.traced: Set[str] = set()     # module-level traced def names
        self.jitted_entries: Set[str] = set()
        self._fn_aliases: Dict[str, str] = {}   # name -> target def name
        self._kernel_refs: Set[str] = set()     # pallas_call first args

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            bound = alias.asname or alias.name.split(".")[0]
            if alias.name == "numpy":
                self.np_aliases.add(bound)
            if alias.name == "jax.numpy":
                self.jnp_aliases.add(alias.asname or "jax.numpy")

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        for alias in node.names:
            if node.module == "jax" and alias.name == "numpy":
                self.jnp_aliases.add(alias.asname or "numpy")
            if alias.name == "enable_x64":
                self.uses_x64 = True

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if any(_is_jit_decorator(d) for d in node.decorator_list):
            self.traced.add(node.name)
            self.jitted_entries.add(node.name)
        self.generic_visit(node)       # pallas_call sites live in bodies

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node: ast.Assign) -> None:
        # `entry = jax.jit(fn)` / `entry = jax.jit(partial(fn, ...))`
        value = node.value
        if isinstance(value, ast.Call) and \
                _dotted(value.func) in ("jax.jit", "jax.pmap"):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.jitted_entries.add(tgt.id)
            target = _callable_target(value.args[0]) if value.args else None
            if target:
                self.traced.add(target)
        else:
            # `kernel = _kernel` / `kernel = functools.partial(_kernel,…)`
            target = _callable_target(value)
            if target:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self._fn_aliases[tgt.id] = target
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        # pallas_call(kernel, ...) / pallas_call(partial(kernel, ...), ...)
        fn = _dotted(node.func)
        if fn in _X64_SCOPES:
            self.uses_x64 = True
        if fn and fn.split(".")[-1] == "pallas_call" and node.args:
            target = _callable_target(node.args[0])
            if target:
                self._kernel_refs.add(target)
        self.generic_visit(node)

    def finish(self) -> None:
        """Resolve pallas kernel references through local aliases."""
        for name in self._kernel_refs:
            self.traced.add(self._fn_aliases.get(name, name))


class _FunctionLint(ast.NodeVisitor):
    """Second pass over one top-level function: emit findings for the
    rule set its traced/host classification selects."""

    def __init__(self, out: List[Finding], rel: str, info: _ModuleInfo,
                 symbol: str, traced: bool,
                 static_names: Optional[Set[str]] = None):
        self.out = out
        self.rel = rel
        self.info = info
        self.static_names = static_names or set()
        self.symbol_stack = [symbol]
        self.traced_stack = [traced]
        self.x64_depth = 0
        # host-wrapper bookkeeping for the retrace rules
        self.calls_jitted = False
        self.calls_pad = False
        self.calls_bucket = False
        self.literal_arg_sites: List[ast.Call] = []

    # ------------------------------------------------------------ utils

    @property
    def traced(self) -> bool:
        return self.traced_stack[-1]

    @property
    def symbol(self) -> str:
        return self.symbol_stack[0]      # fingerprint on the root symbol

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.out.append(Finding(
            rule=rule, path=self.rel, line=getattr(node, "lineno", 0),
            symbol=".".join(self.symbol_stack), message=message))

    def _np_root(self, node: ast.AST) -> Optional[str]:
        while isinstance(node, ast.Attribute):
            node = node.value
        if isinstance(node, ast.Name) and node.id in self.info.np_aliases:
            return node.id
        return None

    _NARROW_DTYPES = frozenset(
        {"float32", "float16", "bfloat16", "int32", "int16", "int8",
         "uint32", "uint16", "uint8", "bool_"})

    def _explicit_narrow_dtype(self, call: ast.Call) -> bool:
        """True when the upload passes an explicit sub-64-bit dtype
        (``jnp.asarray(x, jnp.float32)`` / ``dtype=jnp.int32``): the
        narrowing is intentional, so the x64 extent is irrelevant.  An
        explicit 64-bit dtype still hazards — outside ``enable_x64`` it
        silently produces the 32-bit type."""
        for expr in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(expr, ast.Attribute) and \
                    expr.attr in self._NARROW_DTYPES:
                return True
        return False

    def _static_expr(self, node: ast.AST) -> bool:
        """True when coercing ``node`` is trace-time safe: constants,
        names declared in ``static_argnames``, ``len(...)``, and
        shape/ndim/dtype attribute reads (static under jit)."""
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id in self.static_names
        if isinstance(node, ast.Attribute):
            return node.attr in ("shape", "ndim", "dtype", "size")
        if isinstance(node, ast.Subscript):
            return self._static_expr(node.value)
        if isinstance(node, ast.BinOp):
            return (self._static_expr(node.left)
                    and self._static_expr(node.right))
        if isinstance(node, ast.Call):
            fn = _dotted(node.func)
            return fn == "len" or (fn or "").split(".")[-1] in (
                "bit_length",)
        return False

    # ------------------------------------------------------- structure

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        nested_traced = self.traced or \
            any(_is_jit_decorator(d) for d in node.decorator_list)
        self.symbol_stack.append(node.name)
        self.traced_stack.append(nested_traced)
        self.generic_visit(node)
        self.traced_stack.pop()
        self.symbol_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_With(self, node: ast.With) -> None:
        is_x64 = any(
            isinstance(item.context_expr, ast.Call)
            and _dotted(item.context_expr.func) in _X64_SCOPES
            for item in node.items)
        self.x64_depth += is_x64
        self.generic_visit(node)
        self.x64_depth -= is_x64

    # ----------------------------------------------------- traced rules

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self.traced:
            root = self._np_root(node)
            if root is not None:
                self._emit("host-np-call", node,
                           f"`{root}.{node.attr}` inside traced code — "
                           "host numpy does not trace; use jnp (or hoist "
                           "to the host wrapper)")
                return           # don't double-report nested chain parts
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = _dotted(node.func)
        last = fn.split(".")[-1] if fn else None

        if self.traced:
            if fn == "print":
                self._emit("host-print", node,
                           "print() inside traced code runs at trace "
                           "time only — use jax.debug.print")
            if fn in _COERCE_BUILTINS and node.args and \
                    not self._static_expr(node.args[0]):
                self._emit("host-scalar-coerce", node,
                           f"{fn}() concretizes a traced value (host "
                           "sync under jit, error under scan)")
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _COERCE_METHODS:
                self._emit("host-scalar-coerce", node,
                           f".{node.func.attr}() concretizes a traced "
                           "value — device->host sync in the hot path")
        else:
            # host-wrapper bookkeeping (reported at function close)
            if last in registry.BUCKET_HELPERS:
                self.calls_bucket = True
            if last == "pad" and self._np_root(node.func) is not None:
                self.calls_pad = True
            if isinstance(node.func, ast.Name) and \
                    node.func.id in self.info.jitted_entries:
                self.calls_jitted = True
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    if isinstance(arg, ast.Constant) and \
                            isinstance(arg.value, (int, float, bool)):
                        self.literal_arg_sites.append(node)
                        break
            if self.info.uses_x64 and self.x64_depth == 0 and \
                    last in _UPLOAD_FNS:
                root = node.func
                while isinstance(root, ast.Attribute):
                    root = root.value
                if isinstance(root, ast.Name) and \
                        root.id in self.info.jnp_aliases and \
                        not self._explicit_narrow_dtype(node):
                    self._emit(
                        "jnp-upload-outside-x64", node,
                        f"jnp.{last} outside an enable_x64 block in a "
                        "float64-parity module — float64 operands "
                        "silently downcast to float32")
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        if self.traced:
            it = node.iter
            safe = (isinstance(it, (ast.List, ast.Tuple, ast.Constant))
                    or (isinstance(it, ast.Call)
                        and _dotted(it.func) in _SAFE_ITER_CALLS))
            if not safe:
                self._emit("py-loop-over-array", node,
                           "Python for over a runtime value inside "
                           "traced code — unrolls per element or "
                           "concretizes; use lax.scan/vmap")
        self.generic_visit(node)

    def _check_branch(self, node, kind: str) -> None:
        for sub in ast.walk(node.test):
            if isinstance(sub, ast.Call):
                attr = (sub.func.attr
                        if isinstance(sub.func, ast.Attribute) else
                        sub.func.id if isinstance(sub.func, ast.Name)
                        else None)
                if attr in ("any", "all", "item") or (
                        attr == "bool" and sub.args
                        and not isinstance(sub.args[0], ast.Constant)):
                    self._emit(
                        "py-branch-on-array", node,
                        f"`{kind}` on `.{attr}()` of a traced value — "
                        "Python control flow concretizes; use "
                        "jnp.where/lax.cond")
                    return

    def visit_If(self, node: ast.If) -> None:
        if self.traced:
            self._check_branch(node, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        if self.traced:
            self._check_branch(node, "while")
        self.generic_visit(node)

    # ------------------------------------------------------------ close

    def finish(self, node: ast.FunctionDef) -> None:
        for site in self.literal_arg_sites:
            self._emit("retrace-literal-arg", site,
                       "bare Python scalar passed to a jitted entry — "
                       "weak-typed constants retrace per value; wrap in "
                       "jnp.asarray with an explicit dtype")
        if self.calls_jitted and self.calls_pad and not self.calls_bucket:
            self._emit("retrace-unbucketed-pad", node,
                       "pads operands for a jitted entry without a "
                       "registered bucket helper "
                       f"({', '.join(registry.BUCKET_HELPERS)}) — every "
                       "distinct N compiles a new executable")


def lint_source(source: str, rel: str, *,
                extra_traced: Sequence[str] = ()) -> List[Finding]:
    """Lint one jit-extent module's source text."""
    tree = ast.parse(source, filename=rel)
    info = _ModuleInfo()
    info.visit(tree)
    info.finish()
    info.traced |= set(extra_traced)

    out: List[Finding] = []

    def lint_def(node: ast.FunctionDef, qual: str) -> None:
        lint = _FunctionLint(out, rel, info, qual,
                             traced=node.name in info.traced
                             or qual in info.traced,
                             static_names=_static_argnames(node))
        # visit the body (not the def itself, to keep the stack flat)
        for stmt in node.body:
            lint.visit(stmt)
        lint.finish(node)

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            lint_def(node, node.name)
        elif isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    lint_def(item, f"{node.name}.{item.name}")
    return out


def jit_extent_files(root: pathlib.Path) -> List[pathlib.Path]:
    files: List[pathlib.Path] = []
    for pattern in registry.JIT_EXTENT_GLOBS:
        files.extend(sorted(root.glob(pattern)))
    return files


def lint_tree(root: pathlib.Path) -> List[Finding]:
    """Lint every registered jit-extent module under ``root`` (the repo
    root containing ``src/``)."""
    out: List[Finding] = []
    extra: Dict[str, Sequence[str]] = registry.EXTRA_TRACED
    for path in jit_extent_files(root):
        rel = path.relative_to(root).as_posix()
        out.extend(lint_source(path.read_text(), rel,
                               extra_traced=extra.get(rel, ())))
    return out
