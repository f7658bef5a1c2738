"""reprolint rules RPL001-RPL006: this repo's JAX/Pallas contracts.

Each rule machine-enforces a convention the ROADMAP records (and PRs
1-5 paid for the hard way).  None of these misuses *crash* — they
silently corrupt numbers (stale plan-cache hits, reshuffled PRNG
draws, interpret-mode "serving") or regress startup — which is exactly
why they need a linter rather than a runtime check.  See docs/lint.md
for the rule-by-rule rationale and the suppression syntax.
"""
from __future__ import annotations

import ast
import os
from typing import Iterator

from repro.analysis.core import FileContext, Rule, register_rule

# --------------------------------------------------------------------------
# RPL001 — version-sensitive JAX APIs must route through repro.compat
# --------------------------------------------------------------------------

# Banned dotted path -> the compat entry point that replaces it.
_COMPAT_WRAPPED = {
    "jax.shard_map": "repro.compat.shard_map",
    "jax.experimental.shard_map": "repro.compat.shard_map",
    "jax.sharding.AbstractMesh": "repro.compat.make_abstract_mesh",
    "jax.experimental.enable_x64": "repro.compat.enable_x64",
    "jax.enable_x64": "repro.compat.enable_x64",
}

# Calling these inside a try/except is the capability-probe pattern;
# the probes are centralised (and cached, and trace-safe) in compat.
_PROBE_TARGETS = {
    "jax.lax.linalg.tridiagonal_solve":
        "repro.compat.has_batched_tridiagonal_solve",
}


def _banned_path(path: str | None) -> str | None:
    if path is None:
        return None
    for banned in _COMPAT_WRAPPED:
        if path == banned or path.startswith(banned + "."):
            return banned
    return None


@register_rule
class CompatRouting(Rule):
    code = "RPL001"
    name = "compat-routing"
    rationale = ("Version-sensitive JAX APIs (shard_map, AbstractMesh, "
                 "enable_x64, backend capability probes) are wrapped in "
                 "repro/compat.py; direct use reintroduces the exact "
                 "version breaks PR 1 fixed.")

    def check(self, ctx: FileContext) -> Iterator[tuple[int, int, str]]:
        if ctx.is_compat:
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    b = _banned_path(a.name)
                    if b:
                        yield (node.lineno, node.col_offset,
                               f"direct import of {a.name}; use "
                               f"{_COMPAT_WRAPPED[b]} instead")
            elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                    and node.module:
                for a in node.names:
                    full = (node.module if a.name == "*"
                            else f"{node.module}.{a.name}")
                    b = _banned_path(full) or _banned_path(node.module)
                    if b:
                        yield (node.lineno, node.col_offset,
                               f"direct import of {full}; use "
                               f"{_COMPAT_WRAPPED[b]} instead")
            elif isinstance(node, (ast.Attribute, ast.Name)):
                b = _banned_path(ctx.expand(node))
                if b and not self._is_sub_attribute(ctx, node):
                    yield (node.lineno, node.col_offset,
                           f"direct use of {b}; use "
                           f"{_COMPAT_WRAPPED[b]} instead")
            elif isinstance(node, ast.Try):
                yield from self._probe_findings(ctx, node)

    @staticmethod
    def _is_sub_attribute(ctx: FileContext, node: ast.AST) -> bool:
        # Suppress duplicate findings on the inner Name/Attribute parts
        # of one banned chain: only the *outermost* matching node (and
        # the import that bound it) gets reported.  Cheap check: a Name
        # whose bare id doesn't expand to a banned path by itself was
        # reached as part of a larger Attribute chain and is reported
        # there.
        if isinstance(node, ast.Name):
            return _banned_path(ctx.aliases.get(node.id)) is None
        return False

    @staticmethod
    def _probe_findings(ctx: FileContext,
                        try_node: ast.Try) -> Iterator[tuple[int, int, str]]:
        for stmt in try_node.body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                path = ctx.expand(node.func) or ""
                for target, wrap in _PROBE_TARGETS.items():
                    if path == target or path.endswith("." + target):
                        yield (node.lineno, node.col_offset,
                               f"hand-rolled backend capability probe "
                               f"({target} inside try/except); use "
                               f"{wrap} instead")


# --------------------------------------------------------------------------
# RPL002 — no tracer escapes inside jit/shard_map-decorated functions
# --------------------------------------------------------------------------

_ESCAPE_BUILTINS = {"float", "int", "bool"}
_ESCAPE_CALLS = {"numpy.asarray", "numpy.array"}


def _is_traced_decorator(ctx: FileContext, dec: ast.expr) -> bool:
    """Does this decorator jit- or shard_map-wrap the function?"""
    if isinstance(dec, ast.Call):
        path = ctx.expand(dec.func) or ""
        if path.split(".")[-1] in ("jit", "shard_map"):
            return True  # jax.jit(...) / compat.shard_map(...) factory
        if path.split(".")[-1] == "partial":
            return any(_is_traced_decorator(ctx, a) for a in dec.args)
        return False
    path = ctx.expand(dec) or ""
    return path.split(".")[-1] in ("jit", "shard_map")


def _constant_like(node: ast.expr) -> bool:
    """Literal-ish expressions a float()/int() cast may legally touch."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp):
        return _constant_like(node.operand)
    if isinstance(node, ast.BinOp):
        return _constant_like(node.left) and _constant_like(node.right)
    return False


@register_rule
class TracerEscape(Rule):
    code = "RPL002"
    name = "tracer-escape"
    rationale = ("float()/int()/bool()/.item()/np.asarray inside a "
                 "jit- or shard_map-decorated function forces a "
                 "concretization: TracerError at best, a silent "
                 "recompile-per-call at worst.")

    def check(self, ctx: FileContext) -> Iterator[tuple[int, int, str]]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if not any(_is_traced_decorator(ctx, d)
                       for d in node.decorator_list):
                continue
            yield from self._escapes(ctx, node)

    @staticmethod
    def _escapes(ctx: FileContext, fn: ast.AST
                 ) -> Iterator[tuple[int, int, str]]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name) \
                    and node.func.id in _ESCAPE_BUILTINS:
                if len(node.args) == 1 and not node.keywords \
                        and not _constant_like(node.args[0]):
                    yield (node.lineno, node.col_offset,
                           f"{node.func.id}() on a non-literal inside a "
                           f"traced function escapes the tracer; compute "
                           f"in jnp or hoist to a static argument")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                yield (node.lineno, node.col_offset,
                       ".item() inside a traced function escapes the "
                       "tracer; return the array and read it outside")
            else:
                path = ctx.expand(node.func)
                if path in _ESCAPE_CALLS:
                    yield (node.lineno, node.col_offset,
                           f"{path}() inside a traced function escapes "
                           f"the tracer; use jnp.asarray or move the "
                           f"conversion outside the jit")


# --------------------------------------------------------------------------
# RPL003 — PRNG key discipline (no reuse, no literal seeds in library)
# --------------------------------------------------------------------------

# jax.random callables that *derive* or *construct* keys rather than
# consuming entropy; everything else under jax.random consumes its key.
_KEY_DERIVERS = {"split", "fold_in", "PRNGKey", "key", "key_data",
                 "wrap_key_data", "clone", "key_impl"}
_KEY_MAKERS = {"jax.random.PRNGKey", "jax.random.key"}

_TERMINAL = (ast.Return, ast.Raise, ast.Break, ast.Continue)


def _sampling_key_arg(ctx: FileContext, call: ast.Call) -> ast.expr | None:
    """The key argument if ``call`` is a jax.random sampling call."""
    path = ctx.expand(call.func)
    if not path or not path.startswith("jax.random."):
        return None
    if path.rsplit(".", 1)[1] in _KEY_DERIVERS:
        return None
    for kw in call.keywords:
        if kw.arg == "key":
            return kw.value
    if call.args and not isinstance(call.args[0], ast.Starred):
        return call.args[0]
    return None


def _bound_names(stmt: ast.stmt) -> set[str]:
    """Names (re)bound by one statement, incl. tuple targets + walrus.

    Compound statements (nested loops, with, try) contribute the binds
    of their whole subtree — ``_KeyTracker._loop`` relies on this to
    see that ``k += 1`` inside an inner loop refreshes a
    ``fold_in(key, k)`` expression consumed there.  def/class/lambda
    bodies bind their own scope and are skipped (a def still binds its
    *name*).
    """
    out: set[str] = set()

    def targets(t):
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                targets(e)
        elif isinstance(t, ast.Starred):
            targets(t.value)

    def visit(node):
        if isinstance(node, ast.Lambda):
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            out.add(node.name)
            return
        if isinstance(node, ast.Assign):
            for t in node.targets:
                targets(t)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets(node.target)
        elif isinstance(node, (ast.For, ast.AsyncFor, ast.NamedExpr,
                               ast.comprehension)):
            targets(node.target)
        elif isinstance(node, (ast.withitem,)) and node.optional_vars:
            targets(node.optional_vars)
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(stmt)
    return out


class _KeyTracker:
    """Per-function linear scan flagging same-key sampling reuse."""

    def __init__(self, ctx: FileContext):
        self.ctx = ctx
        self.findings: list[tuple[int, int, str]] = []

    # used: normalized key expression -> (referenced names, first line)
    def block(self, stmts: list[ast.stmt],
              used: dict[str, tuple[frozenset[str], int]]) -> bool:
        """Scan one statement list; returns True if it always exits."""
        terminated = False
        for stmt in stmts:
            if terminated:
                break  # dead code: don't analyze past a terminal stmt
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.block(stmt.body, {})  # fresh scope
                continue
            if isinstance(stmt, ast.ClassDef):
                self.block(stmt.body, {})
                continue
            if isinstance(stmt, _TERMINAL):
                self._uses(stmt, used)
                terminated = True
                continue
            if isinstance(stmt, ast.If):
                self._expr_uses(stmt.test, used)
                merged: dict[str, tuple[frozenset[str], int]] = {}
                exits = []
                for branch in (stmt.body, stmt.orelse):
                    if not branch:
                        exits.append(False)
                        continue
                    u = dict(used)
                    exits.append(self.block(branch, u))
                    if not exits[-1]:
                        merged.update({k: v for k, v in u.items()
                                       if k not in used})
                used.update(merged)
                terminated = bool(exits) and all(exits) \
                    and len(exits) == 2 and stmt.orelse
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                self._loop(stmt, used)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._expr_uses(item.context_expr, used)
                self.block(stmt.body, used)
                continue
            if isinstance(stmt, ast.Try):
                u = dict(used)
                self.block(stmt.body, u)
                used.update({k: v for k, v in u.items() if k not in used})
                for h in stmt.handlers:
                    self.block(h.body, dict(used))
                self.block(stmt.orelse, used)
                self.block(stmt.finalbody, used)
                continue
            # simple statement: record uses, then apply rebinds
            self._uses(stmt, used)
            for name in _bound_names(stmt):
                for k in [k for k, (names, _) in used.items()
                          if name in names]:
                    del used[k]
        return terminated

    def _loop(self, stmt, used) -> None:
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr_uses(stmt.iter, used)
            loop_bound = _bound_names(ast.Assign(
                targets=[stmt.target], value=ast.Constant(value=None)))
        else:
            self._expr_uses(stmt.test, used)
            loop_bound = set()
        for s in stmt.body:
            loop_bound |= _bound_names(s)
        u = dict(used)
        self.block(stmt.body, u)
        fresh = {k: v for k, v in u.items() if k not in used}
        # A key consumed in the body whose expression is not refreshed
        # by anything the loop rebinds repeats identically every
        # iteration.
        for k, (names, line) in fresh.items():
            if not (names & loop_bound):
                self.findings.append((
                    line, 0,
                    f"PRNG key expression '{k}' is consumed on every "
                    f"loop iteration without an interleaving "
                    f"split/fold_in; derive a per-iteration subkey"))
        used.update(fresh)
        self.block(stmt.orelse, used)

    def _uses(self, stmt: ast.stmt, used) -> None:
        # Collect sampling calls in *this* scope only: a lambda's body
        # runs in its own scope (its key parameter shadows ours), so
        # each lambda is tracked separately with a fresh `used` map.
        calls: list[ast.Call] = []
        lambdas: list[ast.Lambda] = []
        stack: list[ast.AST] = list(ast.iter_child_nodes(stmt))
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Lambda):
                lambdas.append(node)
                continue
            if isinstance(node, ast.Call):
                calls.append(node)
            stack.extend(ast.iter_child_nodes(node))
        for lam in lambdas:
            self._expr_uses(lam.body, {})
        calls.sort(key=lambda c: (c.lineno, c.col_offset))
        for call in calls:
            karg = _sampling_key_arg(self.ctx, call)
            if karg is None:
                continue
            ktext = " ".join(ast.unparse(karg).split())
            names = frozenset(n.id for n in ast.walk(karg)
                              if isinstance(n, ast.Name))
            if ktext in used:
                self.findings.append((
                    call.lineno, call.col_offset,
                    f"PRNG key expression '{ktext}' already consumed by "
                    f"a sampling call at line {used[ktext][1]}; "
                    f"split/fold_in a fresh subkey"))
            else:
                used[ktext] = (names, call.lineno)

    def _expr_uses(self, expr: ast.expr, used) -> None:
        self._uses(ast.Expr(value=expr), used)


@register_rule
class KeyDiscipline(Rule):
    code = "RPL003"
    name = "prng-key-discipline"
    rationale = ("Reusing a PRNG key correlates draws that the "
                 "nonideal-model contract promises are independent; "
                 "literal seeds in library code silently pin "
                 "'randomness' every caller believes is keyed.")

    def check(self, ctx: FileContext) -> Iterator[tuple[int, int, str]]:
        if ctx.is_tests:
            return
        tracker = _KeyTracker(ctx)
        tracker.block(ctx.tree.body, {})
        yield from tracker.findings
        if not ctx.is_library:
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) \
                    and ctx.expand(node.func) in _KEY_MAKERS \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, int):
                yield (node.lineno, node.col_offset,
                       f"literal-seed "
                       f"{ctx.expand(node.func).rsplit('.', 1)[1]}"
                       f"({node.args[0].value}) in library code; thread "
                       f"a caller-supplied key through instead")


# --------------------------------------------------------------------------
# RPL004 — interpret mode is test-only
# --------------------------------------------------------------------------


@register_rule
class InterpretTestOnly(Rule):
    code = "RPL004"
    name = "interpret-test-only"
    rationale = ("pallas_call(interpret=True) executes the kernel body "
                 "block-by-block in Python — orders of magnitude too "
                 "slow for anything but BlockSpec validation in tests; "
                 "an interpret default silently serves through it.")

    def check(self, ctx: FileContext) -> Iterator[tuple[int, int, str]]:
        if ctx.is_tests:
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg == "interpret" \
                            and isinstance(kw.value, ast.Constant) \
                            and kw.value.value is True:
                        yield (kw.value.lineno, kw.value.col_offset,
                               "interpret=True outside tests/; interpret "
                               "mode is test-only validation")
                    elif kw.arg == "impl" \
                            and isinstance(kw.value, ast.Constant) \
                            and kw.value.value == "interpret":
                        yield (kw.value.lineno, kw.value.col_offset,
                               'impl="interpret" outside tests/; '
                               "interpret dispatch is test-only")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._interpret_defaults(node)

    @staticmethod
    def _interpret_defaults(fn) -> Iterator[tuple[int, int, str]]:
        a = fn.args
        pairs = list(zip(a.args[len(a.args) - len(a.defaults):],
                         a.defaults))
        pairs += [(arg, d) for arg, d in zip(a.kwonlyargs, a.kw_defaults)
                  if d is not None]
        for arg, default in pairs:
            if arg.arg != "interpret":
                continue
            if isinstance(default, ast.Constant) \
                    and default.value is False:
                continue
            yield (default.lineno, default.col_offset,
                   f"parameter interpret defaults to "
                   f"{ast.unparse(default)}; interpret dispatch must be "
                   f"an explicit test-only opt-in (default False)")


# --------------------------------------------------------------------------
# RPL005 — no module-level jnp computation
# --------------------------------------------------------------------------


@register_rule
class ImportTimeJnp(Rule):
    code = "RPL005"
    name = "import-time-jnp"
    rationale = ("A module-level jax.numpy call initialises the backend "
                 "and compiles at *import* time, taxing every consumer "
                 "(including the jax-free lint CLI and non-JAX tools).")

    def check(self, ctx: FileContext) -> Iterator[tuple[int, int, str]]:
        yield from self._scan_body(ctx, ctx.tree.body)

    def _scan_body(self, ctx, stmts) -> Iterator[tuple[int, int, str]]:
        for stmt in stmts:
            if isinstance(stmt, ast.ClassDef):
                yield from self._scan_body(ctx, stmt.body)
                continue
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # Defaults and decorators evaluate at import time; the
                # body does not.
                for node in (stmt.args.defaults
                             + [d for d in stmt.args.kw_defaults if d]
                             + stmt.decorator_list):
                    yield from self._calls(ctx, node)
                continue
            yield from self._calls(ctx, stmt)

    @classmethod
    def _calls(cls, ctx, root) -> Iterator[tuple[int, int, str]]:
        # Manual traversal instead of ast.walk: lambda/def bodies nested
        # in an import-time expression are deferred and must be skipped.
        # The root itself is tested too — a function *default* is handed
        # in directly and may itself be the offending Call.
        stack: list[ast.AST] = [root]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, ast.Call):
                path = ctx.expand(node.func) or ""
                if path.startswith("jax.numpy."):
                    yield (node.lineno, node.col_offset,
                           f"module-level {path}() runs at import time "
                           f"(backend init + possible compile); use "
                           f"numpy for constants or build lazily")
            stack.extend(ast.iter_child_nodes(node))


# --------------------------------------------------------------------------
# RPL006 — library code times through repro.telemetry, not time.*
# --------------------------------------------------------------------------


@register_rule
class TelemetryClock(Rule):
    code = "RPL006"
    name = "telemetry-clock"
    rationale = ("Ad-hoc time.time()/perf_counter() calls scattered "
                 "through library code bypass the telemetry layer: their "
                 "readings reach no metric, no trace, and no report "
                 "schema.  repro.telemetry.monotonic/wall_time are the "
                 "same clocks behind one instrumentable front door.")

    BANNED = frozenset({
        "time.time", "time.perf_counter", "time.monotonic",
        "time.perf_counter_ns", "time.monotonic_ns",
        "time.process_time", "time.process_time_ns",
    })

    def check(self, ctx: FileContext) -> Iterator[tuple[int, int, str]]:
        if not ctx.is_library:
            return
        parts = os.path.normpath(os.path.abspath(ctx.path)).split(os.sep)
        if "telemetry" in parts and "repro" in parts:
            return  # the one module allowed to own the raw clocks
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            path = ctx.expand(node.func) or ""
            if path in self.BANNED:
                yield (node.lineno, node.col_offset,
                       f"{path}() in library code; use repro.telemetry"
                       f".monotonic() (durations) or .wall_time() "
                       f"(timestamps) so readings feed the metrics/"
                       f"trace layer")
