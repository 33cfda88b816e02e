"""graftlint — JAX tracing-hygiene static analysis over the package.

The superstep work (docs/SPEC.md §8) exposed a class of bug no unit test
catches until the program runs on a device: host syncs hiding in the hot
loop (a blocking ``device_get`` every iteration), a shared zero-buffer
tripping XLA's donate-twice check (``NormState.create``), and silent retraces that erase the
dispatch-amortization win. Podracer/Anakin-style throughput (PAPERS.md)
is exactly the property "one compiled program, zero host round-trips" —
this module checks it with tooling instead of reviewer vigilance.

Rules (catalog with rationale + examples: docs/ANALYSIS.md):

========  ==============================================================
GL101     Python ``if``/``while``/ternary branching on a traced value
          inside a traced function (concretization error at trace time,
          or a silent per-value retrace if the value is marked static).
GL102     Host/numpy calls on traced values in traced code: ``float()``
          / ``int()`` / ``bool()`` / ``np.*(tracer)`` / ``.item()`` /
          ``.tolist()`` / ``jax.device_get`` — each one is a forced
          device→host sync (or a trace-time error).
GL103     ``random.*`` / ``np.random.*`` inside traced code: host RNG is
          invisible to tracing — the draw is baked in at trace time as a
          constant, silently reused by every later call.
GL104     ``jnp``/``lax`` ops inside a Python ``for`` loop in traced
          code: the loop unrolls into the XLA graph (compile time scales
          with trip count) — the unrolled-scan smell; use ``lax.scan``.
GL105     ``jax.device_get`` / ``block_until_ready`` in a hot-path
          module (driver loop, learner, replay, runners): every one is a
          potential pipeline stall; each accepted use carries a baseline
          justification.
GL106     ``time.*`` / ``datetime.*`` in traced code: trace-time
          nondeterminism baked into the compiled program as a constant.
GL107     One allocation passed to two or more fields of a single
          constructor call (the ``NormState.create`` shared-zeros bug:
          donating a state whose leaves alias one buffer trips XLA's
          "donate the same buffer twice" check at dispatch).
GL108     Module-level import never referenced (dead import).
GL109     Array built OUTSIDE a traced function (module level, or in a
          non-traced builder) and referenced inside one via closure:
          the tracer bakes it into the program as a constant (GP202's
          AST-side companion) — duplicated per executable, silently
          stale if the binding is later updated. Pass it as an
          argument instead.
GL110     A device-boundary wrapper call (``_watched`` / ``_sync_point``
          / ``_dispatch``) whose literal phase is not registered in
          ``obs/spans.KNOWN_PHASES``: the graftscope span/flight
          coverage (and the GL110 check itself) is keyed on that set,
          so an unregistered phase is a dispatch boundary whose hangs
          and failures leave no telemetry trail — register it.
GL111     Bare ``lock.acquire()`` without ``timeout=`` (or
          ``blocking=False``) in a liveness-critical module
          (``LOCK_PATH_GLOBS``: the driver, serve/, the watchdog,
          obs/): a stuck holder wedges the thread with no watchdog
          escape — the PR 4 save_lock class. ``with lock:`` is exempt
          (the idiom for short critical sections).
GL112     Raw ``flax.serialization.msgpack_restore`` /
          ``from_state_dict`` in a driver/serve module
          (``CKPT_PATH_GLOBS``): checkpoint bytes must enter through
          ``utils/checkpoint.py``'s verify path (checksum gate, format
          migration, shard assembly, elastic routing) — a raw
          deserialize dodges all four and resurrects the torn-read and
          stale-format classes the checkpoint layer exists to kill.
          The two standing serve-layer loads (an exported artifact
          blob with its own recorded sha256, and a template restore
          already downstream of ``restore_host_state``) are baselined
          with justifications, not exempted by rule.
========  ==============================================================

Scope and honesty about limits: "traced code" means functions that are
*visibly* traced in the same module — decorated with ``jax.jit`` (incl.
``partial(jax.jit, ...)``) / ``vmap`` / ``grad`` / ``checkpoint`` etc.,
or passed by name into a tracing entry point (``jax.jit(f)``,
``lax.scan(body, ...)``, ``lax.cond``, ``lax.while_loop``, ...), plus
defs nested inside those. There is no transitive call-graph analysis:
a helper only ever called *from* traced code is not scanned. Likewise
"traced value" is a forward dataflow approximation (parameters minus
statics, plus locals assigned from expressions that touch traced names
or ``jax.numpy``/``jax.lax``-namespace calls). False positives are
expected and cheap: suppress a line with ``# graftlint: disable=GL1xx``
or accept it into ``analysis/baseline.json`` with a justification
(``baseline.py``); findings are identified by (rule, path, code-line
text), not line numbers, so unrelated edits don't churn the baseline.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: rule id -> one-line summary (the full catalog lives in docs/ANALYSIS.md)
RULES: Dict[str, str] = {
    "GL101": "Python branch on a traced value inside traced code",
    "GL102": "host/numpy call on a traced value inside traced code",
    "GL103": "host RNG (random.* / np.random.*) inside traced code",
    "GL104": "jnp/lax ops inside a Python for loop (unrolled-scan smell)",
    "GL105": "device_get / block_until_ready in a hot-path module",
    "GL106": "time.* / datetime.* nondeterminism inside traced code",
    "GL107": "one allocation aliased across fields of one constructor",
    "GL108": "dead import (module-level import never referenced)",
    "GL109": "closure-captured array constant in traced code (bake hazard)",
    "GL110": "device-boundary wrapper phase missing from obs span registry",
    "GL111": "bare lock acquire() without timeout in a liveness-critical "
             "module",
    "GL112": "raw checkpoint deserialize outside utils/checkpoint's "
             "verify path",
}

#: driver helper names whose first argument is a span/watchdog phase
#: (run.py). GL110 checks literal phases at their call sites against
#: the span registry parsed from SPAN_REGISTRY_PATH.
SPAN_WRAPPERS = frozenset({"_watched", "_sync_point", "_dispatch"})
#: where the span-phase registry lives (parsed by AST, never imported —
#: the lint CLI stays jax-free and import-free)
SPAN_REGISTRY_PATH = "t2omca_tpu/obs/spans.py"

#: modules whose host syncs are throughput hazards (GL105). Matched with
#: fnmatch against the repo-relative posix path.
HOT_PATH_GLOBS: Tuple[str, ...] = (
    "t2omca_tpu/run.py",
    "t2omca_tpu/learners/*.py",
    "t2omca_tpu/components/episode_buffer.py",
    "t2omca_tpu/components/host_replay.py",
    "t2omca_tpu/runners/*.py",
    # the kernel layer IS the hot path: a device_get/block_until_ready
    # creeping into a kernel wrapper would stall every rollout scan step
    "t2omca_tpu/kernels/*.py",
)

#: modules where an unbounded ``lock.acquire()`` is a liveness hazard
#: (GL111): the driver loop, the serving fleet, the watchdog and the
#: telemetry plane all hold locks across device dispatches — a bare
#: acquire there is the PR 4 save_lock wedge class (a stuck holder
#: silently freezes the process with the watchdog unable to report).
#: Bounded forms — ``acquire(timeout=...)`` / ``acquire(blocking=False)``
#: / ``with lock:`` (the context manager is deliberately exempt: it is
#: the idiom for short critical sections that never span a dispatch) —
#: are fine. Matched with fnmatch like HOT_PATH_GLOBS.
LOCK_PATH_GLOBS: Tuple[str, ...] = (
    "t2omca_tpu/run.py",
    "t2omca_tpu/serve/*.py",
    "t2omca_tpu/utils/watchdog.py",
    "t2omca_tpu/obs/*.py",
)

#: modules where a RAW flax deserialize of checkpoint bytes is a
#: correctness hazard (GL112): the driver and the serving layer consume
#: checkpoints, and ``utils/checkpoint.py`` is the one sanctioned door —
#: its restore path owns the sha256 gate against torn/truncated writes,
#: the v3→v5 format migration chain, partial-save shard assembly and
#: the elastic topology routing (docs/RESILIENCE.md §6). A call that
#: goes straight to ``flax.serialization`` silently skips all of them.
#: utils/checkpoint.py itself is deliberately NOT listed.
CKPT_PATH_GLOBS: Tuple[str, ...] = (
    "t2omca_tpu/run.py",
    "t2omca_tpu/serve/*.py",
)

#: the flax deserializers GL112 polices (alias-resolved dotted names)
_RAW_CKPT_LOADS = frozenset({
    "flax.serialization.msgpack_restore",
    "flax.serialization.from_state_dict",
})

# tracing entry points: wrapping one of these around a function makes its
# body traced code. Canonical (alias-resolved) dotted names.
_TRACE_WRAPPERS = frozenset({
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.jacfwd", "jax.jacrev", "jax.hessian", "jax.checkpoint",
    "jax.remat", "jax.custom_jvp", "jax.custom_vjp", "jax.linearize",
})
# control-flow primitives that trace callables handed to them
_TRACE_CONSUMERS = frozenset({
    "jax.lax.scan", "jax.lax.cond", "jax.lax.while_loop",
    "jax.lax.fori_loop", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.lax.custom_linear_solve",
})
#: calls under these namespaces produce traced arrays (dataflow seed)
_ARRAY_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.nn.", "jax.random.",
                   "jax.scipy.", "jax.ops.")
#: allocation calls whose result must not alias across donated leaves
_ALLOC_NAMES = frozenset(
    f"{ns}.{fn}" for ns in ("jax.numpy", "numpy")
    for fn in ("zeros", "ones", "full", "empty", "zeros_like", "ones_like",
               "full_like", "empty_like", "arange", "eye"))

#: jnp/np-namespace calls that return static metadata, not arrays —
#: capturing one by closure bakes nothing (GL109 exemption)
_NONARRAY_CALLS = frozenset({
    "dtype", "shape", "ndim", "size", "result_type", "promote_types",
    "issubdtype", "iinfo", "finfo", "can_cast", "isscalar"})

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable(?:=(?P<rules>\S+))?")
_SKIP_FILE_RE = re.compile(r"#\s*graftlint:\s*skip-file")


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One lint hit. ``key()`` (rule, path, code) is the baseline
    identity — line numbers shift with every unrelated edit, the quoted
    code line doesn't."""

    path: str          # repo-relative posix path
    line: int
    col: int
    rule: str
    message: str
    code: str          # stripped source line at ``line``

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.code)

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} " \
               f"{self.message}"


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` attribute chain -> "a.b.c" (None for anything else)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _ModuleLinter:
    """One parsed module: alias resolution, traced-region discovery, and
    the rule walks. Produces a deduplicated, line-sorted finding list."""

    def __init__(self, src: str, path: str, hot: Optional[bool] = None,
                 span_phases: Optional[Set[str]] = None):
        self.src = src
        self.path = path
        self.lines = src.splitlines()
        self.tree = ast.parse(src, filename=path)
        self.hot = (any(fnmatch.fnmatch(path, g) for g in HOT_PATH_GLOBS)
                    if hot is None else hot)
        #: registered span phases for GL110 (None = rule disabled: the
        #: registry file was absent or the caller didn't supply one)
        self.span_phases = span_phases
        #: local alias -> canonical module/function dotted path
        self.modmap: Dict[str, str] = {}
        #: function name -> [FunctionDef] (all scopes, by simple name)
        self.defs: Dict[str, List[ast.FunctionDef]] = {}
        #: id(FunctionDef) -> static parameter-name set
        self.statics: Dict[int, Set[str]] = {}
        self.findings: Set[Finding] = set()
        self._collect_imports()
        self._collect_defs()

    # ------------------------------------------------------------ aliases

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.modmap[a.asname] = a.name
                    else:
                        root = a.name.split(".")[0]
                        self.modmap[root] = root
            elif isinstance(node, ast.ImportFrom):
                if node.module is None or node.level:
                    continue        # relative imports: package-internal
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.modmap[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def canonical(self, node: ast.AST) -> Optional[str]:
        """Alias-resolved dotted name of an expression (e.g. with
        ``import jax.numpy as jnp``, ``jnp.zeros`` -> "jax.numpy.zeros");
        None when the expression isn't a name chain."""
        dotted = _dotted(node)
        if dotted is None:
            return None
        root, _, rest = dotted.partition(".")
        base = self.modmap.get(root)
        if base is None:
            return dotted
        return f"{base}.{rest}" if rest else base

    # ------------------------------------------------------ traced region

    def _collect_defs(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, []).append(node)

    def _static_params(self, fn: ast.FunctionDef,
                       call: Optional[ast.Call]) -> Set[str]:
        """static_argnames/static_argnums from a jit decorator or call
        site (literal values only — dynamic specs are invisible to AST)."""
        out: Set[str] = set()
        keywords = list(call.keywords) if call is not None else []
        args = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        for kw in keywords:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value,
                                                                  str):
                        out.add(n.value)
            elif kw.arg == "static_argnums":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value,
                                                                  int):
                        if 0 <= n.value < len(args):
                            out.add(args[n.value])
        return out

    def traced_functions(self) -> List[Tuple[ast.FunctionDef, Set[str]]]:
        """(FunctionDef, static-param-names) for every function this
        module visibly hands to the tracer."""
        marked: Dict[int, Tuple[ast.FunctionDef, Set[str]]] = {}

        def mark(fn: ast.FunctionDef, statics: Set[str]) -> None:
            cur = marked.get(id(fn))
            marked[id(fn)] = (fn, (cur[1] | statics) if cur else statics)

        # decorator route: @jax.jit / @partial(jax.jit, static_argnames=..)
        for fns in self.defs.values():
            for fn in fns:
                for dec in fn.decorator_list:
                    call = dec if isinstance(dec, ast.Call) else None
                    target = call.func if call else dec
                    name = self.canonical(target)
                    if name == "functools.partial" and call and call.args:
                        name = self.canonical(call.args[0])
                    if name in _TRACE_WRAPPERS:
                        mark(fn, self._static_params(fn, call))
        # call-site route: jax.jit(f, ...), lax.scan(body, ...), ...
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self.canonical(node.func)
            if name not in _TRACE_WRAPPERS | _TRACE_CONSUMERS:
                continue
            referenced: Set[str] = set()
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name):
                        referenced.add(sub.id)
            for ref in referenced:
                for fn in self.defs.get(ref, []):
                    mark(fn, self._static_params(fn, node)
                         if name in _TRACE_WRAPPERS else set())
        return list(marked.values())

    # ---------------------------------------------------------- emission

    def emit(self, node: ast.AST, rule: str, message: str) -> None:
        line, col = node.lineno, node.col_offset + 1
        code = (self.lines[line - 1].strip()
                if 0 < line <= len(self.lines) else "")
        m = _SUPPRESS_RE.search(self.lines[line - 1]) \
            if 0 < line <= len(self.lines) else None
        if m:
            named = m.group("rules")
            # bare `disable` suppresses everything on the line; a named
            # list suppresses exactly those rules (case-normalized so a
            # `disable=gl105` typo suppresses GL105, not the whole line)
            if named is None or rule in {r.strip().upper()
                                         for r in named.split(",")}:
                return
        self.findings.add(Finding(path=self.path, line=line, col=col,
                                  rule=rule, message=message, code=code))

    # ------------------------------------------------------ traced rules

    def _is_traced_expr(self, expr: ast.AST, traced: Set[str]) -> bool:
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and n.id in traced:
                return True
            if isinstance(n, ast.Call):
                c = self.canonical(n.func)
                if c and c.startswith(_ARRAY_PREFIXES):
                    return True
        return False

    def _traced_locals(self, fn: ast.FunctionDef, traced: Set[str]) -> Set[str]:
        """Forward dataflow to fixpoint: locals assigned from traced
        expressions become traced. Iterated until the set stops growing
        — the lattice only grows and is bounded by the local-name count,
        so this terminates; a fixed pass count would miss taint chains
        written in reverse definition order (w = z; z = y; y = x)."""
        traced = set(traced)
        while True:
            before = len(traced)
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and node is not fn:
                    continue      # nested defs get their own analysis
                targets: List[ast.expr] = []
                value: Optional[ast.expr] = None
                if isinstance(node, ast.Assign):
                    targets, value = node.targets, node.value
                elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                    targets, value = [node.target], node.value
                elif isinstance(node, ast.For):
                    targets, value = [node.target], node.iter
                if value is None or not self._is_traced_expr(value, traced):
                    continue
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            traced.add(n.id)
            if len(traced) == before:
                break
        return traced

    @staticmethod
    def _static_test(test: ast.expr) -> bool:
        """Branch tests that are static even on tracers: identity
        against None, and isinstance/type checks."""
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return True
        if isinstance(test, ast.Call) and isinstance(test.func, ast.Name) \
                and test.func.id in ("isinstance", "callable", "hasattr"):
            return True
        return False

    def _check_traced_function(self, fn: ast.FunctionDef,
                               inherited: Set[str],
                               statics: Set[str]) -> None:
        params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                  + fn.args.kwonlyargs)}
        for extra in (fn.args.vararg, fn.args.kwarg):
            if extra is not None:
                params.add(extra.arg)
        traced = (params - statics - {"self", "cls"}) | inherited
        traced = self._traced_locals(fn, traced)

        def walk(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    # nested def: traced region too, closure names carry
                    self._check_traced_function(child, traced, set())
                    continue
                if isinstance(child, (ast.If, ast.While)) and \
                        not self._static_test(child.test):
                    if self._is_traced_expr(child.test, traced):
                        kind = ("while" if isinstance(child, ast.While)
                                else "if")
                        self.emit(child, "GL101",
                                  f"Python `{kind}` on a traced value in "
                                  f"traced code — use jnp.where/lax.cond "
                                  f"(or mark the argument static)")
                if isinstance(child, ast.IfExp) and \
                        not self._static_test(child.test) and \
                        self._is_traced_expr(child.test, traced):
                    self.emit(child, "GL101",
                              "ternary on a traced value in traced code "
                              "— use jnp.where")
                if isinstance(child, ast.For):
                    for sub in ast.walk(child):
                        if isinstance(sub, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)):
                            break
                        if isinstance(sub, ast.Call):
                            c = self.canonical(sub.func)
                            if c and c.startswith(("jax.numpy.",
                                                   "jax.lax.", "jax.nn.")):
                                self.emit(
                                    child, "GL104",
                                    f"`{c}` inside a Python for loop in "
                                    f"traced code unrolls into the XLA "
                                    f"graph — use lax.scan/fori_loop")
                                break
                if isinstance(child, ast.Call):
                    self._check_traced_call(child, traced)
                walk(child)

        walk(fn)

    def _check_traced_call(self, call: ast.Call, traced: Set[str]) -> None:
        name = self.canonical(call.func)
        argvals = list(call.args) + [kw.value for kw in call.keywords]
        any_traced_arg = any(self._is_traced_expr(a, traced)
                             for a in argvals)
        if name in ("float", "int", "bool", "complex") and any_traced_arg:
            self.emit(call, "GL102",
                      f"`{name}()` on a traced value forces a host sync "
                      f"(concretization) in traced code")
        elif name in ("jax.device_get", "jax.block_until_ready"):
            self.emit(call, "GL102",
                      f"`{name}` inside traced code is a host round-trip "
                      f"baked into the traced program")
        elif name and name.startswith("numpy.random."):
            self.emit(call, "GL103",
                      f"`{name}` in traced code: host RNG draws become "
                      f"trace-time constants — use jax.random")
        elif name and (name == "random" or name.startswith("random.")):
            self.emit(call, "GL103",
                      f"`{name}` in traced code: host RNG draws become "
                      f"trace-time constants — use jax.random")
        elif name and name.startswith("numpy.") and any_traced_arg:
            self.emit(call, "GL102",
                      f"`{name}` on a traced value in traced code forces "
                      f"a host transfer — use jax.numpy")
        elif name and name.startswith(("time.", "datetime.")):
            self.emit(call, "GL106",
                      f"`{name}` in traced code is trace-time "
                      f"nondeterminism baked in as a constant")
        if isinstance(call.func, ast.Attribute) and \
                call.func.attr in ("item", "tolist") and not call.args and \
                self._is_traced_expr(call.func.value, traced):
            self.emit(call, "GL102",
                      f"`.{call.func.attr}()` on a traced value forces a "
                      f"host sync in traced code")

    # -------------------------------------------- closure-captured consts

    def _is_array_expr(self, expr: ast.AST) -> bool:
        """Expression that visibly builds an array: any call under the
        jax.numpy/jax.lax/numpy namespaces in it — excluding the
        helpers that return static metadata (dtypes, shapes, finfo),
        which are legal and common closure captures."""
        for n in ast.walk(expr):
            if isinstance(n, ast.Call):
                c = self.canonical(n.func)
                if c and (c.startswith(_ARRAY_PREFIXES)
                          or c.startswith("numpy.")) \
                        and c.rsplit(".", 1)[-1] not in _NONARRAY_CALLS:
                    return True
        return False

    def _collect_scopes(self) -> None:
        """Lexical scope tables for GL109 (computed once, on demand):
        per scope (FunctionDef id, or None for module) the set of bound
        names, the subset visibly bound to an array expression (with
        the binding node), and each function's enclosing-scope chain."""
        self._scope_bound: Dict[Optional[int], Set[str]] = {None: set()}
        self._scope_arrays: Dict[Optional[int], Dict[str, ast.AST]] = \
            {None: {}}
        self._scope_chain: Dict[int, Tuple[Optional[int], ...]] = {}
        class_ids: Set[int] = set()

        def bind(scope: Optional[int], name: str) -> None:
            self._scope_bound.setdefault(scope, set()).add(name)

        def walk(node: ast.AST, scope: Optional[int],
                 chain: Tuple[Optional[int], ...]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    bind(scope, child.name)
                    fid = id(child)
                    # closure-visible chain: the current scope joins it
                    # only when it is a real closure scope — a class
                    # body is not one (methods cannot capture class
                    # attributes as free variables)
                    vis = chain if scope in class_ids \
                        else (scope,) + chain
                    self._scope_chain[fid] = vis
                    a = child.args
                    for p in (a.posonlyargs + a.args + a.kwonlyargs):
                        bind(fid, p.arg)
                    for extra in (a.vararg, a.kwarg):
                        if extra is not None:
                            bind(fid, extra.arg)
                    walk(child, fid, vis)
                    continue
                if isinstance(child, ast.ClassDef):
                    bind(scope, child.name)
                    # class-body bindings go to a sentinel scope that no
                    # chain ever includes: `class C: TABLE = jnp.…` is an
                    # attribute (C.TABLE), never a closure capture — it
                    # must neither flag GL109 nor shadow a genuine
                    # module-level binding of the same name
                    class_ids.add(id(child))
                    walk(child, id(child), chain)
                    continue
                if isinstance(child, (ast.Assign, ast.AnnAssign)):
                    targets = (child.targets
                               if isinstance(child, ast.Assign)
                               else [child.target])
                    arrayish = (child.value is not None
                                and self._is_array_expr(child.value))
                    for t in targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                bind(scope, n.id)
                                if arrayish:
                                    self._scope_arrays.setdefault(
                                        scope, {})[n.id] = child
                elif isinstance(child, ast.Name) and \
                        isinstance(child.ctx, (ast.Store, ast.Del)):
                    bind(scope, child.id)
                walk(child, scope, chain)

        walk(self.tree, None, ())

    def _check_closure_consts(self, fn: ast.FunctionDef,
                              traced_ids: Set[int]) -> None:
        """GL109: a name FREE in this traced function whose closure
        capture resolves — through the lexical scope chain — to an
        array built at module scope or in a non-traced builder: it is
        concrete at trace time and gets baked into the compiled program
        as a constant (the weights-captured-by-closure class; GP202
        audits the same hazard on the compiled side). A capture whose
        nearest binder is a function parameter or a traced region is a
        tracer, not a bakeable constant — never flagged. One finding
        per name, at the first reference."""
        local: Set[str] = set(self._scope_bound.get(id(fn), set()))
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)) and node is not fn:
                local.add(node.name)
                if not isinstance(node, ast.ClassDef):
                    # nested-def params shadow outer bindings for every
                    # reference in that def's body — a module-level array
                    # name reused as a scan-body parameter is a tracer
                    # there, not a capture (coarse union: suppressing is
                    # the conservative direction)
                    a = node.args
                    for p in (a.posonlyargs + a.args + a.kwonlyargs):
                        local.add(p.arg)
                    for extra in (a.vararg, a.kwarg):
                        if extra is not None:
                            local.add(extra.arg)
            elif isinstance(node, ast.Name) and \
                    isinstance(node.ctx, (ast.Store, ast.Del)):
                local.add(node.id)
        flagged: Set[str] = set()
        # nested defs are walked here too (they are traced by
        # containment); independently-marked ones get their own pass,
        # and the findings set dedupes the overlap
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)):
                continue
            name = node.id
            if name in local or name in flagged:
                continue
            for scope in self._scope_chain.get(id(fn), (None,)):
                if name not in self._scope_bound.get(scope, set()):
                    continue
                src = self._scope_arrays.get(scope, {}).get(name)
                if src is not None and scope not in traced_ids:
                    flagged.add(name)
                    self.emit(node, "GL109",
                              f"`{name}` is an array built outside this "
                              f"traced function (line {src.lineno}) and "
                              f"captured by closure — trace bakes it in "
                              f"as a program constant; pass it as an "
                              f"argument")
                break                    # nearest binder wins either way

    # ------------------------------------------------- module-scope rules

    def _check_hot_path(self) -> None:
        if not self.hot:
            return
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self.canonical(node.func)
            is_bur = (name == "jax.block_until_ready"
                      or (isinstance(node.func, ast.Attribute)
                          and node.func.attr == "block_until_ready"))
            if name == "jax.device_get" or is_bur:
                what = "jax.device_get" if name == "jax.device_get" \
                    else "block_until_ready"
                self.emit(node, "GL105",
                          f"`{what}` in a hot-path module stalls the "
                          f"dispatch pipeline — move to a cadence "
                          f"boundary or baseline with justification")

    def _check_bare_acquire(self) -> None:
        """GL111: explicit ``<something>.acquire()`` with neither a
        ``timeout=`` nor ``blocking=False`` in a liveness-critical
        module (``LOCK_PATH_GLOBS``). A positional first argument is
        the ``blocking`` flag — ``acquire(False)`` is bounded, any
        other positional form is treated as unbounded. Name-based:
        any ``.acquire`` attribute call counts (Lock, RLock,
        Condition, Semaphore all share the wedge semantics)."""
        if not any(fnmatch.fnmatch(self.path, g)
                   for g in LOCK_PATH_GLOBS):
            return
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "acquire"):
                continue
            if any(kw.arg == "timeout" for kw in node.keywords):
                continue
            if any(kw.arg == "blocking"
                   and isinstance(kw.value, ast.Constant)
                   and kw.value.value is False for kw in node.keywords):
                continue
            if (node.args and isinstance(node.args[0], ast.Constant)
                    and node.args[0].value is False):
                continue
            self.emit(node, "GL111",
                      "bare `.acquire()` without a timeout in a "
                      "liveness-critical module: a stuck holder wedges "
                      "this thread with no watchdog escape (the PR 4 "
                      "save_lock class) — pass `timeout=` and handle "
                      "the False return, use `blocking=False`, or "
                      "baseline with a justification")

    def _check_raw_ckpt_loads(self) -> None:
        """GL112: a raw ``flax.serialization.msgpack_restore`` /
        ``from_state_dict`` call in a checkpoint-consuming module
        (``CKPT_PATH_GLOBS``). Name-based on the alias-resolved dotted
        path, with an attribute fallback for handles the alias map
        cannot see (``flax.serialization as ser``-style chains resolve;
        a bound method stored in a variable does not, and none exist in
        the repo today). Justified standing loads live in the baseline,
        not in a rule exemption — a NEW raw load must argue its case."""
        if not any(fnmatch.fnmatch(self.path, g)
                   for g in CKPT_PATH_GLOBS):
            return
        tails = {name.rsplit(".", 1)[1] for name in _RAW_CKPT_LOADS}
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self.canonical(node.func)
            hit = name in _RAW_CKPT_LOADS or (
                name is None and isinstance(node.func, ast.Attribute)
                and node.func.attr in tails)
            if hit:
                what = name or node.func.attr
                self.emit(node, "GL112",
                          f"raw `{what}` deserializes checkpoint bytes "
                          f"outside utils/checkpoint.py's verify path — "
                          f"no checksum gate, no format migration, no "
                          f"shard assembly, no elastic routing; load "
                          f"through utils/checkpoint (or baseline with "
                          f"a justification for why this surface is "
                          f"already downstream of it)")

    def _check_donation_alias(self) -> None:
        for fns in self.defs.values():
            for fn in fns:
                allocs: Set[str] = set()
                for node in ast.walk(fn):
                    if isinstance(node, ast.Assign) and \
                            isinstance(node.value, ast.Call) and \
                            self.canonical(node.value.func) in _ALLOC_NAMES:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                allocs.add(t.id)
                if not allocs:
                    continue
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    name = self.canonical(node.func)
                    if name and (name.startswith(_ARRAY_PREFIXES)
                                 or name.startswith("numpy.")):
                        continue       # reads may alias; only state
                    counts: Dict[str, int] = {}
                    for a in list(node.args) + [kw.value
                                                for kw in node.keywords]:
                        if isinstance(a, ast.Name) and a.id in allocs:
                            counts[a.id] = counts.get(a.id, 0) + 1
                    for nm, c in counts.items():
                        if c >= 2:
                            self.emit(
                                node, "GL107",
                                f"allocation `{nm}` passed {c}x into one "
                                f"constructor: donated leaves must be "
                                f"distinct buffers (XLA donate-twice "
                                f"check) — allocate per field")

    def _check_span_phases(self) -> None:
        """GL110: every literal phase handed to a device-boundary
        wrapper (``_watched``/``_sync_point``/``_dispatch``) must be in
        the span registry — the graftscope coverage contract. Only
        plain-name calls with a literal first ``phase`` argument are
        checkable; dynamic phases are invisible to AST and skipped
        (none exist in the driver today, and introducing one dodges
        this coverage check — don't)."""
        if self.span_phases is None:
            return
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in SPAN_WRAPPERS):
                continue
            phase = None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                phase = node.args[0].value
            else:
                for kw in node.keywords:
                    if kw.arg == "phase" \
                            and isinstance(kw.value, ast.Constant) \
                            and isinstance(kw.value.value, str):
                        phase = kw.value.value
            if phase is not None and phase not in self.span_phases:
                self.emit(node, "GL110",
                          f"phase {phase!r} passed to "
                          f"`{node.func.id}` is not registered in "
                          f"obs/spans.KNOWN_PHASES — this dispatch "
                          f"boundary has no span/flight coverage "
                          f"contract; add it to the registry")

    def _check_dead_imports(self) -> None:
        if self.path.endswith("__init__.py"):
            return                     # re-export surface: imports ARE use
        imported: Dict[str, ast.AST] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    imported[a.asname or a.name.split(".")[0]] = node
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for a in node.names:
                    if a.name != "*":
                        imported[a.asname or a.name] = node
        used: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            if isinstance(node, ast.Name):
                used.add(node.id)
        for node in ast.walk(self.tree):        # __all__ re-exports count
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in node.targets):
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        used.add(sub.value)
        for name, node in sorted(imported.items()):
            if name not in used:
                self.emit(node, "GL108",
                          f"`{name}` is imported but never used")

    # ------------------------------------------------------------- drive

    def run(self) -> List[Finding]:
        if any(_SKIP_FILE_RE.search(l) for l in self.lines[:10]):
            return []
        marked = self.traced_functions()
        traced_ids: Set[int] = set()
        for fn, _ in marked:
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    traced_ids.add(id(sub))
        self._collect_scopes()
        for fn, statics in marked:
            self._check_traced_function(fn, set(), statics)
            self._check_closure_consts(fn, traced_ids)
        self._check_hot_path()
        self._check_bare_acquire()
        self._check_raw_ckpt_loads()
        self._check_donation_alias()
        self._check_dead_imports()
        self._check_span_phases()
        return sorted(self.findings,
                      key=lambda f: (f.path, f.line, f.col, f.rule))


# ---------------------------------------------------------------- frontend

def collect_span_phases(root: Path) -> Optional[Set[str]]:
    """Parse ``KNOWN_PHASES`` out of the span registry
    (``obs/spans.py``) by AST — never imported, so the lint CLI stays
    import-free. None (GL110 disabled) when the file or the assignment
    is absent; a registry that exists but parses to zero phases is
    still a live (maximally strict) rule."""
    path = Path(root) / SPAN_REGISTRY_PATH
    if not path.is_file():
        return None
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except (OSError, SyntaxError):
        return None
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "KNOWN_PHASES"
                   for t in node.targets):
            continue
        return {n.value for n in ast.walk(node.value)
                if isinstance(n, ast.Constant)
                and isinstance(n.value, str)}
    return None


def lint_source(src: str, path: str = "<memory>",
                hot: Optional[bool] = None,
                span_phases: Optional[Set[str]] = None) -> List[Finding]:
    """Lint one source string (fixture entry point for the tests).
    ``span_phases`` arms GL110 (``lint_package`` supplies the real
    registry; fixtures pass their own set)."""
    return _ModuleLinter(src, path, hot=hot,
                         span_phases=span_phases).run()


def lint_file(path: Path, root: Path,
              span_phases: Optional[Set[str]] = None) -> List[Finding]:
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    return lint_source(path.read_text(), rel, span_phases=span_phases)


def lint_package(root: Path,
                 paths: Optional[Sequence[Path]] = None) -> List[Finding]:
    """Lint every ``*.py`` under ``paths`` (default: ``root/t2omca_tpu``),
    reporting paths relative to ``root`` (the repo root)."""
    root = Path(root)
    if paths is None:
        paths = [root / "t2omca_tpu"]
    span_phases = collect_span_phases(root)
    findings: List[Finding] = []
    for p in paths:
        p = Path(p)
        files: Iterable[Path] = (sorted(p.rglob("*.py")) if p.is_dir()
                                 else [p])
        for f in files:
            findings.extend(lint_file(f, root, span_phases=span_phases))
    return findings
