"""The shipped rules: AST checks for the invariants no unit test can see.

Each rule is a generator over the shared :mod:`model` tree, registered in
:data:`RULES`. Rules are *structural*: they prove properties of the
source (a key is never drawn twice, a guarded attribute is only touched
under its lock, every knob maps to an invoked validator), which is
exactly the class of DP-correctness property that runtime tests cannot
establish — a test observes one execution; the invariant quantifies over
all of them.
"""

import ast
import collections
import math
import re
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Set, Tuple

from pipelinedp_tpu.staticcheck import dataflow
from pipelinedp_tpu.staticcheck import threads as threads_mod
from pipelinedp_tpu.staticcheck.model import CallGraph, Finding, Module

Rule = collections.namedtuple("Rule", ["rule_id", "help", "fn"])

RULES: Dict[str, Rule] = {}


def rule(rule_id: str, help_text: str):
    def deco(fn: Callable[[List[Module]], Iterator[Finding]]):
        RULES[rule_id] = Rule(rule_id, help_text, fn)
        return fn
    return deco


def _walk_no_nested_scopes(root: ast.AST) -> Iterator[ast.AST]:
    """ast.walk (root included) that does not descend into nested
    function/lambda bodies — they are separate scopes, visited on their
    own."""
    stack = [root]
    while stack:
        node = stack.pop()
        yield node
        if node is root or not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


def _function_scopes(tree: ast.AST) -> Iterator[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.Module)):
            yield node


def _stored_names(node: ast.AST) -> Set[str]:
    return {
        n.id
        for n in _walk_no_nested_scopes(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store,
                                                          ast.Del))
    }


# ---------------------------------------------------------------------------
# (1) key-hygiene
# ---------------------------------------------------------------------------

# jax.random functions that CONSUME a key (a draw); split/fold_in DERIVE.
_KEY_DRAWS = frozenset({
    "uniform", "normal", "laplace", "exponential", "bits", "bernoulli",
    "gumbel", "randint", "choice", "permutation", "categorical",
    "truncated_normal", "poisson", "gamma", "beta", "cauchy", "logistic",
    "rademacher", "shuffle", "t", "dirichlet", "multivariate_normal",
})

# The one sanctioned PRNGKey constructor: every other key in product code
# must arrive through the seed plumbing and be derived via split/fold_in.
_SANCTIONED_KEY_CONSTRUCTORS = frozenset({"make_noise_key"})


def _draw_key_name(mod: Module, node: ast.AST) -> Optional[Tuple[str, int]]:
    """(key variable name, line) when node is a jax.random draw keyed by a
    bare variable."""
    if not (isinstance(node, ast.Call) and node.args):
        return None
    name = mod.dotted(node.func)
    if name is None or not name.startswith("jax.random."):
        return None
    if name.rsplit(".", 1)[1] not in _KEY_DRAWS:
        return None
    key = node.args[0]
    if isinstance(key, ast.Name):
        return key.id, node.lineno
    return None


def _check_scope_key_reuse(mod: Module, scope: ast.AST
                           ) -> Iterator[Finding]:
    versions: Dict[str, int] = {}
    # (name, version) -> first draw line.
    seen: Dict[Tuple[str, int], int] = {}

    if isinstance(scope, ast.Lambda):
        draws: Dict[str, int] = {}
        for node in _walk_no_nested_scopes(scope.body):
            hit = _draw_key_name(mod, node)
            if hit is None:
                continue
            name, line = hit
            if name in draws:
                yield Finding(
                    "key-hygiene", mod.rel, line,
                    f"PRNG key {name!r} consumed by a second jax.random "
                    f"draw (first at line {draws[name]}) without an "
                    f"intervening split/fold_in — correlated noise is a "
                    f"privacy failure, not a statistics bug")
            else:
                draws[name] = line
        return

    body = scope.body if not isinstance(scope, ast.Module) else scope.body

    def bump(target: ast.AST) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store,
                                                              ast.Del)):
                versions[n.id] = versions.get(n.id, 0) + 1
                seen.pop((n.id, versions[n.id]), None)

    def expr_draws(node: Optional[ast.AST], loop_stores: Set[str],
                   out: List[Finding]) -> None:
        if node is None:
            return
        for n in _walk_no_nested_scopes(node):
            hit = _draw_key_name(mod, n)
            if hit is None:
                continue
            name, line = hit
            if loop_stores and name not in loop_stores:
                out.append(Finding(
                    "key-hygiene", mod.rel, line,
                    f"PRNG key {name!r} consumed inside a loop without a "
                    f"per-iteration split/fold_in derivation — every "
                    f"iteration draws the same randomness"))
                continue
            ver = versions.get(name, 0)
            if (name, ver) in seen:
                out.append(Finding(
                    "key-hygiene", mod.rel, line,
                    f"PRNG key {name!r} consumed by a second jax.random "
                    f"draw (first at line {seen[(name, ver)]}) without an "
                    f"intervening split/fold_in — correlated noise is a "
                    f"privacy failure, not a statistics bug"))
            else:
                seen[(name, ver)] = line

    def walk(stmts: Iterable[ast.stmt], loop_stores: Set[str],
             out: List[Finding]) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # separate scope / own pass
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                expr_draws(stmt.value, loop_stores, out)
                targets = (stmt.targets
                           if isinstance(stmt, ast.Assign) else
                           [stmt.target])
                for t in targets:
                    bump(t)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                expr_draws(stmt.iter, loop_stores, out)
                inner = loop_stores | _stored_names(stmt)
                bump(stmt.target)
                walk(stmt.body, inner, out)
                walk(stmt.orelse, loop_stores, out)
            elif isinstance(stmt, ast.While):
                expr_draws(stmt.test, loop_stores, out)
                walk(stmt.body, loop_stores | _stored_names(stmt), out)
                walk(stmt.orelse, loop_stores, out)
            elif isinstance(stmt, ast.If):
                expr_draws(stmt.test, loop_stores, out)
                fork = dict(seen)
                walk(stmt.body, loop_stores, out)
                after_body = dict(seen)
                seen.clear()
                seen.update(fork)
                walk(stmt.orelse, loop_stores, out)
                seen.update(after_body)  # post-if reuse collides with either
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    expr_draws(item.context_expr, loop_stores, out)
                    if item.optional_vars is not None:
                        bump(item.optional_vars)
                walk(stmt.body, loop_stores, out)
            elif isinstance(stmt, ast.Try):
                walk(stmt.body, loop_stores, out)
                for handler in stmt.handlers:
                    walk(handler.body, loop_stores, out)
                walk(stmt.orelse, loop_stores, out)
                walk(stmt.finalbody, loop_stores, out)
            else:
                expr_draws(stmt, loop_stores, out)

    out: List[Finding] = []
    walk(body, set(), out)
    yield from out


@rule(
    "key-hygiene",
    "A PRNG key must never be consumed by two jax.random draws without "
    "an intervening split/fold_in, and jax.random.PRNGKey may only be "
    "constructed by the sanctioned seed plumbing (ops/noise.py "
    "make_noise_key) — ad-hoc keys bypass the fold_in(final_key, b) "
    "derivation the bit-identical-retry guarantee rests on.")
def key_hygiene(modules: List[Module]) -> Iterator[Finding]:
    for mod in modules:
        for scope in _function_scopes(mod.tree):
            yield from _check_scope_key_reuse(mod, scope)
        func_stack: List[str] = []

        def visit(node: ast.AST) -> Iterator[Finding]:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                func_stack.append(node.name)
                for child in ast.iter_child_nodes(node):
                    yield from visit(child)
                func_stack.pop()
                return
            if (isinstance(node, ast.Call) and
                    mod.dotted(node.func) == "jax.random.PRNGKey" and
                    not (set(func_stack) &
                         _SANCTIONED_KEY_CONSTRUCTORS)):
                yield Finding(
                    "key-hygiene", mod.rel, node.lineno,
                    "jax.random.PRNGKey constructed outside "
                    "make_noise_key — product keys must come through the "
                    "seed plumbing and be derived via split/fold_in so "
                    "retries and resumes replay the same release")
            for child in ast.iter_child_nodes(node):
                yield from visit(child)

        yield from visit(mod.tree)


# ---------------------------------------------------------------------------
# (2) host-rng
# ---------------------------------------------------------------------------

_GLOBAL_NP_DRAWS = frozenset({
    "rand", "randn", "random", "random_sample", "ranf", "sample",
    "randint", "random_integers", "choice", "shuffle", "permutation",
    "normal", "laplace", "uniform", "binomial", "poisson", "exponential",
    "geometric", "beta", "gamma", "gumbel", "logistic",
    "standard_normal", "standard_cauchy", "standard_exponential", "seed",
    "bytes",
})
_STDLIB_RANDOM_DRAWS = frozenset({
    "random", "randint", "randrange", "uniform", "sample", "choice",
    "choices", "shuffle", "gauss", "normalvariate", "lognormvariate",
    "expovariate", "betavariate", "gammavariate", "triangular",
    "vonmisesvariate", "paretovariate", "weibullvariate", "seed",
    "getrandbits", "randbytes",
})
_RNG_CONSTRUCTORS = frozenset({
    "numpy.random.default_rng", "numpy.random.RandomState",
    "numpy.random.Generator", "random.Random", "random.SystemRandom",
})


@rule(
    "host-rng",
    "No hidden host randomness: module-global RNG instances and draws "
    "from the process-global numpy/stdlib RNG state are forbidden — "
    "noise and sampling must come from explicitly seeded, injectable "
    "generators (or the device-side counter-based keys), or a resumed "
    "job cannot replay the same release.")
def host_rng(modules: List[Module]) -> Iterator[Finding]:
    for mod in modules:
        in_function = [False]

        def visit(node: ast.AST) -> Iterator[Finding]:
            entered = isinstance(node, (ast.FunctionDef,
                                        ast.AsyncFunctionDef, ast.Lambda))
            if entered:
                in_function.append(True)
            if isinstance(node, ast.Call):
                name = mod.dotted(node.func)
                if name in _RNG_CONSTRUCTORS and not in_function[-1]:
                    yield Finding(
                        "host-rng", mod.rel, node.lineno,
                        f"module-global RNG instance ({name}) — shared "
                        f"mutable RNG state hides the seed; use an "
                        f"explicitly seeded, injectable generator "
                        f"created at (or passed into) the call site")
                elif name is not None and name.startswith("numpy.random."):
                    fn = name.rsplit(".", 1)[1]
                    if fn in _GLOBAL_NP_DRAWS:
                        yield Finding(
                            "host-rng", mod.rel, node.lineno,
                            f"{name}() draws from numpy's process-global "
                            f"RNG — route through an injectable "
                            f"np.random.Generator (sampling_utils / the "
                            f"module's seeded rng) instead")
                elif name is not None and name.startswith("random."):
                    fn = name.split(".", 1)[1]
                    if fn in _STDLIB_RANDOM_DRAWS:
                        yield Finding(
                            "host-rng", mod.rel, node.lineno,
                            f"stdlib {name}() draws from the "
                            f"process-global RNG — use an injectable "
                            f"generator instead")
            for child in ast.iter_child_nodes(node):
                yield from visit(child)
            if entered:
                in_function.pop()

        yield from visit(mod.tree)


# ---------------------------------------------------------------------------
# (3) host-transfer
# ---------------------------------------------------------------------------

_TRANSFER_CALLS = frozenset({
    "numpy.asarray", "numpy.array", "numpy.ascontiguousarray",
    "jax.device_get",
})
_TRANSFER_METHODS = frozenset({"item", "tolist"})
# The sanctioned device->host routing points: transfers INSIDE these
# functions are the implementation of the routing itself.
_SANCTIONED_FETCH_FUNCS = frozenset({
    ("pipelinedp_tpu/parallel/mesh.py", "host_fetch"),
    ("pipelinedp_tpu/parallel/mesh.py", "sync_fetch"),
})


# Device-resident modules beyond the parallel/ and ops/ trees: the
# streaming executor's staging queue hands device arrays between stages,
# so a smuggled np.asarray there would serialize the exact overlap the
# module exists to create.
_DEVICE_RESIDENT_FILES = frozenset({
    "pipelinedp_tpu/runtime/pipeline.py",
    # The hash-device encode module: raw hash columns stream host ->
    # device once, codes are assigned inside jit, and the ONLY sanctioned
    # device->host traffic is the unique-count control scalars and the
    # O(kept) decode prefetch — all through mesh.host_fetch.
    "pipelinedp_tpu/device_encode.py",
})


def _is_device_resident(mod: Module) -> bool:
    dirs = mod.parts[:-1]
    return ("parallel" in dirs or "ops" in dirs or
            mod.rel in _DEVICE_RESIDENT_FILES)


@rule(
    "host-transfer",
    "Device-resident modules (parallel/, ops/, runtime/pipeline.py) "
    "must not smuggle host "
    "transfers: np.asarray/np.array/jax.device_get/.item()/.tolist() on "
    "device values block on a device->host copy. Route control-plane "
    "fetches through mesh.host_fetch (retried, watchdog-guarded, "
    "traced); O(kept)/O(D) post-drain staging is baselined with a note "
    "or suppressed with a reason — the runtime counterpart is "
    "reshard.forbid_row_fetches.")
def host_transfer(modules: List[Module]) -> Iterator[Finding]:
    for mod in modules:
        if not _is_device_resident(mod):
            continue
        func_stack: List[str] = []

        def visit(node: ast.AST) -> Iterator[Finding]:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                func_stack.append(node.name)
                for child in ast.iter_child_nodes(node):
                    yield from visit(child)
                func_stack.pop()
                return
            sanctioned = any((mod.rel, fn) in _SANCTIONED_FETCH_FUNCS
                             for fn in func_stack)
            if isinstance(node, ast.Call) and not sanctioned:
                name = mod.dotted(node.func)
                if name in _TRANSFER_CALLS:
                    yield Finding(
                        "host-transfer", mod.rel, node.lineno,
                        f"{name}() in a device-resident module forces a "
                        f"blocking device->host transfer — route through "
                        f"mesh.host_fetch, or suppress with a reason / "
                        f"baseline with a note if the volume is bounded "
                        f"(O(kept), O(D))")
                elif (isinstance(node.func, ast.Attribute) and
                      node.func.attr in _TRANSFER_METHODS and
                      not node.args and not node.keywords):
                    yield Finding(
                        "host-transfer", mod.rel, node.lineno,
                        f".{node.func.attr}() in a device-resident module "
                        f"forces a blocking device->host transfer — "
                        f"route through mesh.host_fetch, or suppress "
                        f"with a reason")
            for child in ast.iter_child_nodes(node):
                yield from visit(child)

        yield from visit(mod.tree)


# ---------------------------------------------------------------------------
# (3b) dtype-discipline
# ---------------------------------------------------------------------------

# Reductions whose accumulator dtype defaults to the input dtype: on an
# f32 column that is an implicit f32 accumulator — the exact overflow /
# precision-loss channel the numeric-armor sentinel exists to catch.
_ACCUM_REDUCTIONS = frozenset({
    "jax.numpy.sum", "jax.numpy.cumsum", "jax.numpy.prod",
})
_NARROW_INT_DTYPES = frozenset({
    "int8", "int16", "int32", "uint8", "uint16", "uint32",
})


def _astype_target_leaf(call: ast.Call, mod: Module) -> Optional[str]:
    """The dtype leaf name of an ``.astype(X)`` call, if determinable."""
    if not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    name = mod.dotted(arg)
    if name:
        return name.rsplit(".", 1)[-1]
    return None


@rule(
    "dtype-discipline",
    "Numeric dtype discipline in device-resident modules (parallel/, "
    "ops/, runtime/pipeline.py): reductions (jnp.sum/jnp.cumsum/"
    "jnp.prod) must declare their accumulator — dtype= or an explicit "
    ".astype on the operand — because an implicit f32 accumulator "
    "silently loses integer exactness past 2**24 and wraps at scale; "
    "fractional float literals must not be ==/!= compared against "
    "computed values (an accumulated or noised float is never reliably "
    "equal to a decimal literal — compare integers or use a tolerance); "
    "and a reduction must not be .astype-narrowed to an integer dtype "
    "in the same expression (probe or clip the accumulator first, or "
    "suppress with the proven range).")
def dtype_discipline(modules: List[Module]) -> Iterator[Finding]:
    for mod in modules:
        if not _is_device_resident(mod):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                name = mod.dotted(node.func) or ""
                if name in _ACCUM_REDUCTIONS:
                    has_dtype = any(kw.arg == "dtype"
                                    for kw in node.keywords)
                    operand_cast = bool(node.args) and (
                        isinstance(node.args[0], ast.Call) and
                        isinstance(node.args[0].func, ast.Attribute) and
                        node.args[0].func.attr == "astype")
                    if not has_dtype and not operand_cast:
                        leaf = name.rsplit(".", 1)[-1]
                        yield Finding(
                            "dtype-discipline", mod.rel, node.lineno,
                            f"jnp.{leaf}() without an explicit accumulator "
                            f"dtype in a device-resident module — an "
                            f"implicit f32 accumulator loses integer "
                            f"exactness past 2**24; pass dtype= (or cast "
                            f"the operand with .astype) to make the "
                            f"accumulation width a reviewed decision")
                elif (isinstance(node.func, ast.Attribute) and
                      node.func.attr == "astype" and
                      isinstance(node.func.value, ast.Call) and
                      (mod.dotted(node.func.value.func) or "")
                      in _ACCUM_REDUCTIONS):
                    target = _astype_target_leaf(node, mod)
                    if target in _NARROW_INT_DTYPES:
                        yield Finding(
                            "dtype-discipline", mod.rel, node.lineno,
                            f"reduction result .astype({target}) in one "
                            f"expression — the accumulator is truncated "
                            f"un-probed; check the range (or clip) before "
                            f"narrowing, or suppress with the proven "
                            f"bound")
            elif isinstance(node, ast.Compare):
                operands = [node.left] + list(node.comparators)
                frac_lit = any(
                    isinstance(o, ast.Constant) and
                    isinstance(o.value, float) and
                    math.isfinite(o.value) and
                    o.value != int(o.value)
                    for o in operands)
                if frac_lit and any(isinstance(op, (ast.Eq, ast.NotEq))
                                    for op in node.ops):
                    yield Finding(
                        "dtype-discipline", mod.rel, node.lineno,
                        "==/!= against a fractional float literal in a "
                        "device-resident module — computed f32 values "
                        "(accumulated, noised, rescaled) are never "
                        "reliably equal to a decimal literal; compare "
                        "integers, exact sentinels (0.0), or use a "
                        "tolerance")


# ---------------------------------------------------------------------------
# (4) lock-discipline
# ---------------------------------------------------------------------------

def _guarded_decl(mod: Module, stmt: ast.stmt
                  ) -> Optional[Tuple[str, Tuple[str, ...]]]:
    """Parses ``_GUARDED_BY = guarded_by("<lock>", "<attr>", ...)``."""
    if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and
            isinstance(stmt.targets[0], ast.Name) and
            stmt.targets[0].id == "_GUARDED_BY" and
            isinstance(stmt.value, ast.Call)):
        return None
    callee = mod.dotted(stmt.value.func) or ""
    if callee.rsplit(".", 1)[-1] != "guarded_by":
        return None
    names = []
    for arg in stmt.value.args:
        if not (isinstance(arg, ast.Constant) and
                isinstance(arg.value, str)):
            return None
        names.append(arg.value)
    if len(names) < 2:
        return None
    return names[0], tuple(names[1:])


def _with_locks(mod: Module, stmt: ast.stmt, self_form: bool) -> Set[str]:
    locks: Set[str] = set()
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        for item in stmt.items:
            name = mod.dotted(item.context_expr)
            if name is None:
                continue
            if self_form and name.startswith("self."):
                locks.add(name[len("self."):])
            elif not self_form and "." not in name:
                locks.add(name)
    return locks


def _check_guarded_body(mod: Module, body: Iterable[ast.stmt], lock: str,
                        attrs: Tuple[str, ...], self_form: bool,
                        where: str) -> Iterator[Finding]:

    def visit(node: ast.AST, held: bool) -> Iterator[Finding]:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            # A nested function/lambda runs later, outside the lock that
            # was held at definition time.
            body_nodes = (node.body if isinstance(node.body, list)
                          else [node.body])
            for child in body_nodes:
                yield from visit(child, False)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            acquires = lock in _with_locks(mod, node, self_form)
            for item in node.items:
                yield from visit(item.context_expr, held)
                if item.optional_vars is not None:
                    yield from visit(item.optional_vars, held)
            for child in node.body:
                yield from visit(child, held or acquires)
            return
        touched = None
        if self_form:
            if (isinstance(node, ast.Attribute) and
                    isinstance(node.value, ast.Name) and
                    node.value.id == "self" and node.attr in attrs):
                touched = f"self.{node.attr}"
        else:
            if isinstance(node, ast.Name) and node.id in attrs:
                touched = node.id
        if touched is not None and not held:
            lock_name = f"self.{lock}" if self_form else lock
            yield Finding(
                "lock-discipline", mod.rel, node.lineno,
                f"{touched} is declared guarded_by({lock!r}) in {where} "
                f"but is touched outside `with {lock_name}:` — a silent "
                f"data race with the watchdog/monitor threads")
        for child in ast.iter_child_nodes(node):
            yield from visit(child, held)

    for stmt in body:
        yield from visit(stmt, False)


@rule(
    "lock-discipline",
    "Attributes declared via `_GUARDED_BY = guarded_by(\"_lock\", ...)` "
    "(runtime/concurrency.py) must only be touched inside "
    "`with <lock>:`. __init__ and module-scope initialization are "
    "exempt (construction happens-before publication); helpers whose "
    "caller holds the lock carry a def-line suppression with a reason.")
def lock_discipline(modules: List[Module]) -> Iterator[Finding]:
    for mod in modules:
        # Module-scope declaration: guarded globals, checked inside every
        # function of the module (module-level statements initialize).
        for stmt in mod.tree.body:
            decl = _guarded_decl(mod, stmt)
            if decl is None:
                continue
            lock, attrs = decl
            for node in mod.tree.body:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef)):
                    yield from _check_guarded_body(
                        mod, [node], lock, attrs, self_form=False,
                        where=f"module {mod.rel}")
        # Class-scope declarations: guarded instance attributes.
        for cls in ast.walk(mod.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for stmt in cls.body:
                decl = _guarded_decl(mod, stmt)
                if decl is None:
                    continue
                lock, attrs = decl
                for method in cls.body:
                    if not isinstance(method, (ast.FunctionDef,
                                               ast.AsyncFunctionDef)):
                        continue
                    if method.name == "__init__":
                        continue
                    yield from _check_guarded_body(
                        mod, method.body, lock, attrs, self_form=True,
                        where=f"class {cls.name}")


# ---------------------------------------------------------------------------
# (5) jit-boundary
# ---------------------------------------------------------------------------

_SHAPE_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "sharding",
                          "aval"})


def _jit_decorator_info(mod: Module, dec: ast.AST
                        ) -> Optional[Tuple[Set[str], Set[int]]]:
    """(static_argnames, static_argnums) when `dec` jit-compiles, else
    None. Handles @jax.jit and @functools.partial(jax.jit, ...)."""
    if mod.dotted(dec) == "jax.jit":
        return set(), set()
    if not isinstance(dec, ast.Call):
        return None
    callee = mod.dotted(dec.func)
    if callee == "jax.jit":
        call = dec
    elif callee in ("functools.partial", "partial") and dec.args and \
            mod.dotted(dec.args[0]) == "jax.jit":
        call = dec
    else:
        return None
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.add(n.value)
        elif kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.add(n.value)
    return names, nums


# Attribution wrappers the jit-boundary rule accepts: probe_jit (the
# traced-dispatch probe) and aot_probe (runtime/aot.py — probe_jit plus
# the AOT executable cache; it wraps probe_jit internally, so its
# compiles and dispatches carry the same per-entry-point attribution).
_PROBE_WRAPPERS = frozenset({"probe_jit", "aot_probe"})

# The one module allowed to call .lower().compile() directly: it IS the
# attribution wrapper (aot_probe counts the compile into
# trace.note_compile + aot_cache_misses before executing).
_AOT_REL = "pipelinedp_tpu/runtime/aot.py"


def _probe_wrapped_names(mod: Module) -> Set[str]:
    wrapped: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            callee = mod.dotted(node.func) or ""
            if callee.rsplit(".", 1)[-1] in _PROBE_WRAPPERS:
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        wrapped.add(arg.id)
    return wrapped


def _lowered_compile_findings(mod: Module) -> Iterator[Finding]:
    """AOT entry points: a ``<jitted>.lower(...).compile()`` chain
    builds an executable that dispatches OUTSIDE jit's probed path —
    unless it lives in runtime/aot.py (whose aot_probe is the sanctioned
    attribution wrapper), its compiles and dispatches are invisible to
    the compile/dispatch accounting and the aot_cache_hits/misses
    evidence."""
    if mod.rel == _AOT_REL:
        return
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and
                isinstance(node.func, ast.Attribute) and
                node.func.attr == "compile"):
            continue
        inner = node.func.value
        if (isinstance(inner, ast.Call) and
                isinstance(inner.func, ast.Attribute) and
                inner.func.attr == "lower"):
            yield Finding(
                "jit-boundary", mod.rel, node.lineno,
                "bare .lower().compile() builds an AOT executable "
                "outside runtime/aot.py — its compile seconds and "
                "dispatches are invisible to the per-entry-point "
                "attribution and the aot_cache_hits/misses evidence; "
                "wrap the entry point in rt_aot.aot_probe(name, fn, "
                "static_argnames=...) instead")


def _traced_if_findings(mod: Module, fn: ast.AST, traced: Set[str]
                        ) -> Iterator[Finding]:
    shielded: Set[ast.AST] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) and \
                node.attr in _SHAPE_ATTRS and \
                isinstance(node.value, ast.Name):
            shielded.add(node.value)
        if isinstance(node, ast.Compare) and any(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            for n in ast.walk(node):
                if isinstance(n, ast.Name):
                    shielded.add(n)
    for node in _walk_no_nested_scopes(fn):
        if not isinstance(node, (ast.If, ast.While)):
            continue
        for n in ast.walk(node.test):
            if isinstance(n, ast.Name) and n.id in traced and \
                    n not in shielded:
                yield Finding(
                    "jit-boundary", mod.rel, node.lineno,
                    f"Python `if`/`while` on traced argument {n.id!r} "
                    f"inside a jitted body — tracing evaluates this once "
                    f"at compile time, not per value; use lax.cond / "
                    f"jnp.where, or declare the argument static")
                break


@rule(
    "jit-boundary",
    "Every jax.jit/pjit entry point must be wrapped in trace.probe_jit "
    "or runtime/aot.aot_probe (compile/dispatch attribution — an "
    "unwrapped kernel's compiles are invisible in the e2e gap "
    "accounting), jitted bodies must not branch in Python on traced "
    "arguments, and .lower().compile() AOT executables may only be "
    "built inside runtime/aot.py, whose aot_probe carries the same "
    "attribution.")
def jit_boundary(modules: List[Module]) -> Iterator[Finding]:
    for mod in modules:
        wrapped = _probe_wrapped_names(mod)
        yield from _lowered_compile_findings(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                info = _jit_decorator_info(mod, dec)
                if info is None:
                    continue
                static_names, static_nums = info
                if node.name not in wrapped:
                    yield Finding(
                        "jit-boundary", mod.rel, node.lineno,
                        f"jit entry point {node.name!r} is not wrapped "
                        f"in trace.probe_jit — its compiles and "
                        f"dispatches are invisible to the compile/"
                        f"dispatch attribution (reassign: {node.name} = "
                        f"rt_trace.probe_jit({node.name!r}, "
                        f"{node.name}))")
                args = node.args
                traced = {
                    a.arg
                    for i, a in enumerate(args.posonlyargs + args.args)
                    if a.arg not in static_names and i not in static_nums
                } | {a.arg for a in args.kwonlyargs
                     if a.arg not in static_names}
                yield from _traced_if_findings(mod, node, traced)
                break


# ---------------------------------------------------------------------------
# (6a) registry-drift
# ---------------------------------------------------------------------------

_TELEMETRY_REL = "pipelinedp_tpu/runtime/telemetry.py"

# Declaration helper -> the metric kind it declares. Bare Metric(...)
# calls carry their kind as the second positional argument.
_DECL_HELPERS = {"_counter": "counter", "_gauge": "gauge"}


def _declared_metrics(mod: Module) -> Dict[str, Tuple[int, str]]:
    """{metric name: (line, kind)} declared in telemetry.REGISTRY."""
    declared: Dict[str, Tuple[int, str]] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = (mod.dotted(node.func) or "").rsplit(".", 1)[-1]
        if not node.args or not isinstance(node.args[0], ast.Constant) \
                or not isinstance(node.args[0].value, str):
            continue
        if callee in _DECL_HELPERS:
            declared[node.args[0].value] = (node.lineno,
                                            _DECL_HELPERS[callee])
        elif callee == "Metric":
            kind = "counter"
            if len(node.args) > 1 and \
                    isinstance(node.args[1], ast.Constant) and \
                    isinstance(node.args[1].value, str):
                kind = node.args[1].value
            declared[node.args[0].value] = (node.lineno, kind)
    return declared


def _metric_call_literals(modules: List[Module], func_name: str
                          ) -> Dict[str, List[Tuple[str, int]]]:
    """First-arg string literals of every `<func_name>("...")` call."""
    found: Dict[str, List[Tuple[str, int]]] = {}
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            func = node.func
            hit = (isinstance(func, ast.Attribute) and
                   func.attr == func_name) or \
                  (isinstance(func, ast.Name) and func.id == func_name)
            if not hit:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str)\
                    and arg.value.isidentifier():
                found.setdefault(arg.value, []).append(
                    (mod.rel, node.lineno))
    return found


def _recorded_literals(modules: List[Module]
                       ) -> Dict[str, List[Tuple[str, int]]]:
    return _metric_call_literals(modules, "record")


@rule(
    "registry-drift",
    "telemetry.REGISTRY and the source tree must agree in BOTH "
    "directions and BOTH kinds: every telemetry.record(\"name\") / "
    "set_gauge(\"name\") literal names a declared metric of the right "
    "kind (counter / gauge), every declared counter is recorded "
    "somewhere and every declared gauge is set somewhere — dead metrics "
    "mislead receipt and scrape readers, undeclared ones fork the "
    "namespace.")
def registry_drift(modules: List[Module]) -> Iterator[Finding]:
    telemetry = next((m for m in modules if m.rel == _TELEMETRY_REL), None)
    if telemetry is None:
        return
    declared = _declared_metrics(telemetry)
    for func_name, want_kind, other_api in (
            ("record", "counter", "set_gauge"),
            ("set_gauge", "gauge", "record")):
        used = _metric_call_literals(modules, func_name)
        for name, sites in sorted(used.items()):
            rel, line = sites[0]
            if name not in declared:
                yield Finding(
                    "registry-drift", rel, line,
                    f"telemetry.{func_name}({name!r}) has no REGISTRY "
                    f"declaration — declare it (name, kind, help) in "
                    f"runtime/telemetry.py first")
            elif declared[name][1] != want_kind:
                yield Finding(
                    "registry-drift", rel, line,
                    f"telemetry.{func_name}({name!r}) targets a metric "
                    f"declared as a {declared[name][1]} — use "
                    f"{other_api}() or fix the declaration's kind")
        for name, (line, kind) in sorted(declared.items()):
            if kind == want_kind and name not in used:
                verb = ("records" if want_kind == "counter" else "sets")
                yield Finding(
                    "registry-drift", _TELEMETRY_REL, line,
                    f"REGISTRY declares {want_kind} {name!r} but no "
                    f"source file {verb} it — a dead metric misleads "
                    f"receipt readers; drop it or wire it up")


# ---------------------------------------------------------------------------
# (6b) knob-validation
# ---------------------------------------------------------------------------

_ENTRY_REL = "pipelinedp_tpu/runtime/entry.py"
_VALIDATORS_REL = "pipelinedp_tpu/input_validators.py"
_BACKEND_REL = "pipelinedp_tpu/pipeline_backend.py"
_SERVICE_REL = "pipelinedp_tpu/service/service.py"

# Runtime knob -> the input_validators function that must vet it.
KNOB_VALIDATORS: Dict[str, str] = {
    "retry": "validate_retry_policy",
    "journal": "validate_journal",
    "timeout_s": "validate_timeout_s",
    "watchdog": "validate_watchdog",
    "elastic": "validate_elastic",
    "min_devices": "validate_min_devices",
    "job_id": "validate_job_id",
    "trace": "validate_trace",
    "pipeline_depth": "validate_pipeline_depth",
    "encode_threads": "validate_encode_threads",
    "encode_mode": "validate_encode_mode",
    "num_processes": "validate_num_processes",
    "coordinator_address": "validate_coordinator_address",
    "metrics_port": "validate_metrics_port",
    "metrics_path": "validate_metrics_path",
    # Warm-path knobs (PR 14): the AOT executable cache and the
    # compute/drain overlap. The driver-level `overlap` route selector
    # shares the backend validator (validated in runtime/entry.py's
    # wrapper).
    "aot": "validate_aot",
    "overlap_drain": "validate_overlap_drain",
    "overlap": "validate_overlap_drain",
    # Multi-tenant service knobs (validated in
    # DPAggregationService.__init__ — the service API boundary).
    "max_concurrent_jobs": "validate_max_concurrent_jobs",
    "tenant_budget_epsilon": "validate_tenant_budget_epsilon",
    "queue_timeout_s": "validate_queue_timeout_s",
    "shed_watermark_fraction": "validate_shed_watermark_fraction",
    # Megabatched-serving knobs (PR 16): the coalescing tier's switch,
    # window and lane cap — a bad window or lane cap would silently
    # stall every identical-spec job in an unfillable batch window.
    "batching": "validate_batching",
    "batch_window_ms": "validate_batch_window_ms",
    "max_batch_jobs": "validate_max_batch_jobs",
    # Fleet-operations knobs (PR 17): scale-UP admission and the
    # service's drain window — an unvetted grow switch or drain
    # timeout changes failure semantics (which jobs finish vs cancel
    # during a rolling restart), so both go through the validators.
    "elastic_grow": "validate_elastic_grow",
    "drain_timeout_s": "validate_drain_timeout_s",
    # Chaos/robustness knobs (PR 18): the per-job deadline is failure
    # semantics by definition — it decides which jobs settle CANCELLED —
    # and is validated at its own API boundary
    # (DPAggregationService.submit).
    "deadline_s": "validate_deadline_s",
    # Numeric-armor knobs (PR 19): the accumulation discipline decides
    # whether overflow wraps or fails closed, and the snapping-grid
    # floor changes which values a release can legally take — both are
    # release semantics, validated in TPUBackend.__init__.
    "numeric_mode": "validate_numeric_mode",
    "snap_grid_bits": "validate_snap_grid_bits",
    # PLD-accounting knobs (PR 20): the accounting mode decides which
    # spend number admission charges (privacy semantics by definition),
    # and the discretization interval sizes the loss grid every
    # composed bound is computed on — both validated at the service
    # API boundary (and in TenantLedger / PLDBudgetAccountant).
    "tenant_accounting": "validate_tenant_accounting",
    "pld_discretization": "validate_pld_discretization",
}

# Data-plane parameters: configuration, not failure semantics — adding
# one here is a deliberate reviewed decision, not a default.
KNOB_EXEMPT = frozenset({
    # driver data/geometry knobs
    "block_partitions", "row_chunk", "secure_tables", "reshard",
    # TPUBackend configuration
    "mesh", "max_partitions", "noise_seed", "secure_noise",
    "large_partition_threshold",
    # DPAggregationService configuration (data-plane: where ledgers
    # live and what the shed check divides by — not failure semantics)
    "ledger_dir", "memory_limit_bytes",
})

_DRIVER_FUNCS: Dict[str, Tuple[str, ...]] = {
    "pipelinedp_tpu/parallel/large_p.py": (
        "aggregate_blocked", "aggregate_blocked_sharded",
        "select_partitions_blocked", "select_partitions_blocked_sharded"),
    "pipelinedp_tpu/parallel/sharded.py": (
        "sharded_aggregate_arrays", "sharded_select_partitions"),
}


def _keyword_knobs(fn: ast.FunctionDef) -> Dict[str, int]:
    """Defaulted-positional + keyword-only parameter names -> line."""
    knobs: Dict[str, int] = {}
    args = fn.args
    defaulted = args.args[len(args.args) - len(args.defaults):] \
        if args.defaults else []
    for a in defaulted:
        knobs[a.arg] = a.lineno
    for a in args.kwonlyargs:
        knobs[a.arg] = a.lineno
    return knobs


def _find_funcdef(mod: Module, name: str,
                  cls: Optional[str] = None) -> Optional[ast.FunctionDef]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef) and cls is not None and \
                node.name == cls:
            for sub in node.body:
                if isinstance(sub, ast.FunctionDef) and sub.name == name:
                    return sub
        elif cls is None and isinstance(node, ast.FunctionDef) and \
                node.name == name:
            return node
    return None


def _invoked_validators(node: ast.AST, mod: Module) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            callee = mod.dotted(n.func) or ""
            leaf = callee.rsplit(".", 1)[-1]
            if leaf.startswith("validate_"):
                out.add(leaf)
    return out


@rule(
    "knob-validation",
    "Every runtime knob on the drivers, the shared runtime_entry wrapper "
    "and TPUBackend must map to an input_validators.validate_* function "
    "that exists and is invoked at the API boundary (runtime/entry.py "
    "for drivers, TPUBackend.__init__ for the backend); stale map "
    "entries are flagged in the reverse direction.")
def knob_validation(modules: List[Module]) -> Iterator[Finding]:
    by_rel = {m.rel: m for m in modules}
    entry = by_rel.get(_ENTRY_REL)
    validators_mod = by_rel.get(_VALIDATORS_REL)
    backend_mod = by_rel.get(_BACKEND_REL)

    defined_validators = None
    if validators_mod is not None:
        defined_validators = {
            node.name
            for node in ast.walk(validators_mod.tree)
            if isinstance(node, ast.FunctionDef)
        }

    all_knobs: Dict[str, Tuple[str, int]] = {}

    def check_knobs(knobs: Dict[str, int], rel: str, owner: str,
                    invoked: Set[str], boundary: str) -> Iterator[Finding]:
        for knob, line in sorted(knobs.items()):
            all_knobs.setdefault(knob, (rel, line))
            if knob in KNOB_EXEMPT:
                continue
            if knob not in KNOB_VALIDATORS:
                yield Finding(
                    "knob-validation", rel, line,
                    f"{owner} grew a runtime knob {knob!r} with no "
                    f"validator mapping — add input_validators."
                    f"validate_{knob}, map it in staticcheck/rules.py "
                    f"KNOB_VALIDATORS and invoke it at {boundary} (or "
                    f"exempt it deliberately as a data-plane parameter)")
                continue
            validator = KNOB_VALIDATORS[knob]
            if defined_validators is not None and \
                    validator not in defined_validators:
                yield Finding(
                    "knob-validation", rel, line,
                    f"input_validators.{validator} (mapped for knob "
                    f"{knob!r}) does not exist")
            if validator not in invoked:
                yield Finding(
                    "knob-validation", rel, line,
                    f"{boundary} never invokes {validator} for "
                    f"{knob!r} — the knob skips validation at the API "
                    f"boundary")

    if entry is not None:
        wrapper = _find_funcdef(entry, "wrapper")
        entry_invoked = _invoked_validators(entry.tree, entry)
        if wrapper is not None:
            yield from check_knobs(
                _keyword_knobs(wrapper), entry.rel,
                "the runtime_entry wrapper", entry_invoked,
                "runtime/entry.py")
        for rel, names in _DRIVER_FUNCS.items():
            driver_mod = by_rel.get(rel)
            if driver_mod is None:
                continue
            for name in names:
                fn = _find_funcdef(driver_mod, name)
                if fn is None:
                    yield Finding(
                        "knob-validation", rel, 1,
                        f"driver {name!r} expected in {rel} but not "
                        f"found — update staticcheck/rules.py "
                        f"_DRIVER_FUNCS")
                    continue
                yield from check_knobs(
                    _keyword_knobs(fn), rel, f"driver {name}",
                    entry_invoked, "runtime/entry.py")

    if backend_mod is not None:
        init = _find_funcdef(backend_mod, "__init__", cls="TPUBackend")
        if init is not None:
            knobs = {a.arg: a.lineno
                     for a in init.args.args if a.arg != "self"}
            knobs.update(_keyword_knobs(init))
            knobs.pop("self", None)
            yield from check_knobs(
                knobs, backend_mod.rel, "TPUBackend",
                _invoked_validators(init, backend_mod),
                "TPUBackend.__init__")

    # The multi-tenant service is its own API boundary: every defaulted
    # DPAggregationService.__init__ parameter is a runtime knob under
    # the same discipline as TPUBackend's.
    service_mod = by_rel.get(_SERVICE_REL)
    if service_mod is not None:
        init = _find_funcdef(service_mod, "__init__",
                             cls="DPAggregationService")
        if init is not None:
            yield from check_knobs(
                _keyword_knobs(init), service_mod.rel,
                "DPAggregationService",
                _invoked_validators(init, service_mod),
                "DPAggregationService.__init__")
        # submit() is a second service boundary: its keyword-only
        # knobs (deadline_s) gate per-job failure semantics and must
        # be vetted before the job is ever queued.
        submit = _find_funcdef(service_mod, "submit",
                               cls="DPAggregationService")
        if submit is not None:
            yield from check_knobs(
                _keyword_knobs(submit), service_mod.rel,
                "DPAggregationService.submit",
                _invoked_validators(submit, service_mod),
                "DPAggregationService.submit")

    # Reverse direction: a mapping whose knob no longer exists anywhere
    # is stale — it would silently pass while guarding nothing.
    if entry is not None and backend_mod is not None:
        for knob in sorted(set(KNOB_VALIDATORS) - set(all_knobs)):
            yield Finding(
                "knob-validation", _ENTRY_REL, 1,
                f"KNOB_VALIDATORS maps {knob!r} -> "
                f"{KNOB_VALIDATORS[knob]!r} but no driver, wrapper or "
                f"TPUBackend parameter with that name exists — stale "
                f"mapping; drop it or restore the knob")


# ---------------------------------------------------------------------------
# (7) broad-except
# ---------------------------------------------------------------------------

_BLE_OK = re.compile(r"#\s*noqa:\s*BLE001\s*[-—]\s*\S")


@rule(
    "broad-except",
    "`except Exception` / bare `except:` must carry a classification "
    "comment (`# noqa: BLE001 - <why this breadth is safe>`): the "
    "runtime's retry/degradation machinery depends on exceptions being "
    "CLASSIFIED (transient/oom/timeout/device-fatal), and an "
    "unclassified broad except swallows the classification.")
def broad_except(modules: List[Module]) -> Iterator[Finding]:
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = node.type is None
            if node.type is not None:
                types = node.type.elts if isinstance(node.type, ast.Tuple)\
                    else [node.type]
                broad = any(mod.dotted(t) == "Exception" for t in types)
            if not broad:
                continue
            if _BLE_OK.search(mod.line_text(node.lineno)):
                continue
            yield Finding(
                "broad-except", mod.rel, node.lineno,
                "broad `except Exception` without a classification "
                "comment — classify-and-reraise (see runtime/retry.py "
                "sites) or annotate `# noqa: BLE001 - <reason>`")


# ---------------------------------------------------------------------------
# Interprocedural families (8-10): one shared call graph per pass
# ---------------------------------------------------------------------------

# Rules 8-10 are flows across functions; they share one CallGraph (and
# the dataflow engines built on it) per analyze() pass instead of each
# re-deriving it. The cache is keyed by the identities of the Module
# objects (core.analyze hands each rule a fresh list wrapping the SAME
# parsed modules).
_GRAPH_CACHE: "collections.OrderedDict[tuple, CallGraph]" = \
    collections.OrderedDict()


def _call_graph(modules: List[Module]) -> CallGraph:
    key = tuple(id(m) for m in modules)
    hit = _GRAPH_CACHE.get(key)
    if hit is None:
        # The entry pins the module list: while it lives, no id in the
        # key can be recycled by the allocator for a different Module.
        hit = (CallGraph(modules), list(modules))
        _GRAPH_CACHE[key] = hit
        while len(_GRAPH_CACHE) > 4:
            _GRAPH_CACHE.popitem(last=False)
    return hit[0]


# ---------------------------------------------------------------------------
# (8) release-taint
# ---------------------------------------------------------------------------

_EXECUTOR_REL = "pipelinedp_tpu/executor.py"
_COLUMNAR_REL = "pipelinedp_tpu/columnar.py"
_INGEST_REL = "pipelinedp_tpu/ingest.py"
_OBSERVABILITY_REL = "pipelinedp_tpu/runtime/observability.py"

# Raw-row sources: functions whose return carries un-noised row-column
# data (encoded codes, partition vocabularies, raw value columns).
TAINT_SOURCES: Dict[Tuple[str, str], str] = {
    (_COLUMNAR_REL, "factorize"): "columnar.factorize",
    (_COLUMNAR_REL, "encode_with_vocab"): "columnar.encode_with_vocab",
    (_COLUMNAR_REL, "encode_columns"): "columnar.encode_columns",
    (_COLUMNAR_REL, "encode"): "columnar.encode",
    (_INGEST_REL, "chunk_factorize"): "ingest.chunk_factorize",
    (_INGEST_REL, "stream_encode_columns"):
        "ingest.stream_encode_columns",
    (_INGEST_REL, "encode_shard"): "ingest.encode_shard",
    (_INGEST_REL, "encode_local_shard_to_mesh"):
        "ingest.encode_local_shard_to_mesh",
    (_INGEST_REL, "ChunkedVocabEncoder.encode"):
        "ChunkedVocabEncoder.encode",
    (_INGEST_REL, "ChunkedVocabEncoder.merge"):
        "ChunkedVocabEncoder.merge",
    (_INGEST_REL, "ChunkedVocabEncoder.vocabulary"):
        "ChunkedVocabEncoder.vocabulary",
}

# DP release points: values coming out of these are noised and/or
# DP-threshold-selected — taint is cleared. (Bounding/offset kernels are
# deliberately NOT here: bounded-but-un-noised stats are still raw.)
TAINT_SANITIZERS: Set[Tuple[str, str]] = {
    (_EXECUTOR_REL, "aggregate_kernel"),
    (_EXECUTOR_REL, "select_kept_pair_stream"),
    (_EXECUTOR_REL, "select_partitions_kernel"),
    (_EXECUTOR_REL, "sweep_kernel"),
    ("pipelinedp_tpu/parallel/large_p.py", "_block_kernel_dev"),
    ("pipelinedp_tpu/parallel/large_p.py", "_selection_block_kernel"),
    ("pipelinedp_tpu/parallel/large_p.py", "_sharded_block_kernel"),
    ("pipelinedp_tpu/parallel/large_p.py", "_sharded_selection_block"),
    ("pipelinedp_tpu/parallel/large_p.py", "_sharded_select_compact"),
    ("pipelinedp_tpu/ops/selection_ops.py", "sample_keep_decisions"),
    ("pipelinedp_tpu/ops/noise.py", "laplace_noise"),
    ("pipelinedp_tpu/ops/noise.py", "gaussian_noise"),
    ("pipelinedp_tpu/ops/noise.py", "additive_noise"),
    ("pipelinedp_tpu/dp_computations.py", "apply_laplace_mechanism"),
    ("pipelinedp_tpu/dp_computations.py", "apply_gaussian_mechanism"),
    ("pipelinedp_tpu/dp_computations.py", "_add_random_noise"),
    ("pipelinedp_tpu/dp_computations.py", "add_noise_vector"),
    ("pipelinedp_tpu/dp_computations.py", "compute_dp_var"),
}

# Mechanism methods sanitize wherever the receiver came from.
TAINT_SANITIZER_ATTRS = frozenset({
    "add_noise", "compute_mean", "add_noise_vector",
})
TAINT_SANITIZER_DOTTED = frozenset()

# Cardinality/metadata declassifiers (module docstring of dataflow.py).
TAINT_DECLASS_CALLS = frozenset({"len", "bool", "isinstance", "hasattr",
                                 "id", "type", "range"})
TAINT_DECLASS_ATTRS = frozenset({"shape", "ndim", "size", "nbytes",
                                 "dtype", "n_rows", "n_partitions",
                                 "itemsize"})

# Driver release functions: the engine-facing normalization points whose
# return/yield IS the released output — anything tainted leaving here
# un-noised is a privacy leak, not a telemetry nit.
TAINT_RELEASE_FUNCS: Set[Tuple[str, str]] = {
    (_EXECUTOR_REL, "lazy_aggregate"),
    (_EXECUTOR_REL, "lazy_select_partitions"),
}

# Observability entry points that serialize their arguments off-process.
_OBS_EXPORT_FUNCS = frozenset({
    "export_process_state", "write_pod_rollup", "record_mechanism",
    "persist_odometer", "account_bytes", "release_bytes",
})


def _taint_sink_args(graph, mod, scope, call, callee):
    """Sink detector for release-taint (dataflow.TaintConfig.sink_args):
    [(sink label, [arg expressions whose taint is a finding])]."""
    hits = []
    dotted = mod.dotted(call.func) or ""
    leaf = dotted.rsplit(".", 1)[-1]
    attr = call.func.attr if isinstance(call.func, ast.Attribute) else None
    kw_exprs = [kw.value for kw in call.keywords]
    if callee is not None and callee.rel == _OBSERVABILITY_REL and \
            callee.qualname in _OBS_EXPORT_FUNCS:
        hits.append((f"observability export ({callee.qualname})",
                     list(call.args) + kw_exprs))
        return hits
    if leaf == "span" and (
            (callee is not None and
             callee.rel == "pipelinedp_tpu/runtime/trace.py") or
            ".span" in dotted or dotted == "span"):
        hits.append(("trace-span attr", kw_exprs))
    elif attr == "set" and not call.args and call.keywords:
        # Span token attr update: sp.set(bytes=..., rows=...).
        hits.append(("trace-span attr", kw_exprs))
    elif leaf == "instant":
        hits.append(("trace instant attr", kw_exprs))
    elif leaf == "record" and call.args and \
            isinstance(call.args[0], ast.Constant):
        hits.append(("telemetry counter attr",
                     list(call.args[1:]) + kw_exprs))
    elif leaf == "set_gauge" and len(call.args) >= 2:
        hits.append(("telemetry gauge value", [call.args[1]]))
    elif attr == "put" and len(call.args) == 3:
        # BlockJournal.put(job_id, key, record): the persisted payload.
        hits.append(("journal payload", [call.args[1], call.args[2]]))
    return hits


@rule(
    "release-taint",
    "Values derived from raw row columns (columnar/ingest sources) must "
    "pass through a registered DP mechanism (dp_computations mechanisms, "
    "the noised/selection kernels) before reaching an export sink: "
    "trace-span/instant attrs, telemetry.record/set_gauge values, "
    "journal payloads, observability exports, or the drivers' released "
    "return values. Interprocedural: findings carry the full "
    "source->sink call path. Sizes (len/.shape/.nbytes/...) are "
    "cardinality metadata and declassify.")
def release_taint(modules: List[Module]) -> Iterator[Finding]:
    graph = _call_graph(modules)
    cfg = dataflow.TaintConfig(
        sources=TAINT_SOURCES,
        sanitizers=TAINT_SANITIZERS,
        sanitizer_attrs=TAINT_SANITIZER_ATTRS,
        sanitizer_dotted=TAINT_SANITIZER_DOTTED,
        declass_calls=TAINT_DECLASS_CALLS,
        declass_attrs=TAINT_DECLASS_ATTRS,
        release_funcs=TAINT_RELEASE_FUNCS,
        sink_args=_taint_sink_args,
    )
    for f in sorted(dataflow.run_taint(graph, cfg),
                    key=lambda f: (f.rel, f.line, f.sink,
                                   f.origin.label)):
        yield Finding(
            "release-taint", f.rel, f.line,
            f"un-noised raw-row-derived value reaches {f.sink} — route "
            f"it through a registered DP mechanism first, or suppress "
            f"with a reason naming the sanctioned release. Path: "
            f"{f.origin.render_path()} -> {f.sink} ({f.rel}:{f.line})")


# ---------------------------------------------------------------------------
# (9) lock-order
# ---------------------------------------------------------------------------

# Syntactic blocking patterns: calls that can wait on another thread,
# the scheduler, a device or the disk. Receiver-string constants are
# excluded by the engine (",".join() is not Thread.join()).
LOCK_BLOCKING_ATTRS = frozenset({
    "join", "start", "result", "acquire", "wait", "serve_forever",
    "shutdown", "fsync",
})
LOCK_BLOCKING_DOTTED = frozenset({
    "time.sleep", "os.fsync", "subprocess.run", "subprocess.check_call",
    "subprocess.check_output",
})
LOCK_BLOCKING_FUNCS: Set[Tuple[str, str]] = {
    ("pipelinedp_tpu/parallel/mesh.py", "host_fetch"),
    ("pipelinedp_tpu/parallel/mesh.py", "sync_fetch"),
}

_CALLER_HOLDS_RE = re.compile(r"caller holds", re.IGNORECASE)


def _declared_locks(modules: List[Module]
                    ) -> Dict[Tuple[str, str], Set[str]]:
    """{(rel, cls-or-""): lock names} from guarded_by declarations."""
    declared: Dict[Tuple[str, str], Set[str]] = {}
    for mod in modules:
        for stmt in mod.tree.body:
            decl = _guarded_decl(mod, stmt)
            if decl is not None:
                declared.setdefault((mod.rel, ""), set()).add(decl[0])
        for cls in ast.walk(mod.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for stmt in cls.body:
                decl = _guarded_decl(mod, stmt)
                if decl is not None:
                    declared.setdefault((mod.rel, cls.name),
                                        set()).add(decl[0])
    return declared


def _declared_guarded_attrs(modules: List[Module]
                            ) -> Set[Tuple[str, str, str]]:
    """{(rel, cls-or-"", attr)} of every attribute a ``_GUARDED_BY``
    declaration covers — lock-discipline territory the thread-escape
    rule must not duplicate."""
    out: Set[Tuple[str, str, str]] = set()
    for mod in modules:
        for stmt in mod.tree.body:
            decl = _guarded_decl(mod, stmt)
            if decl is not None:
                out.update((mod.rel, "", attr) for attr in decl[1])
        for cls in ast.walk(mod.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for stmt in cls.body:
                decl = _guarded_decl(mod, stmt)
                if decl is not None:
                    out.update((mod.rel, cls.name, attr)
                               for attr in decl[1])
    return out


def _lock_name(lock: "dataflow.LockId") -> str:
    rel, cls, name = lock
    owner = f"{cls}." if cls else ""
    return f"{rel}:{owner}{name}"


def _caller_holds_helpers(graph: CallGraph
                          ) -> Dict[Tuple[str, str], str]:
    """Functions whose def line carries a lock-discipline suppression
    documented as 'caller holds <lock>': {func key: lock attr name}."""
    out: Dict[Tuple[str, str], str] = {}
    for info in graph.iter_functions():
        mod = graph.modules[info.rel]
        sup = mod.suppression_for("lock-discipline", info.node.lineno)
        if sup is None or not sup.reason or \
                not _CALLER_HOLDS_RE.search(sup.reason):
            continue
        declared = _declared_locks([mod]).get(
            (info.rel, info.cls or ""), set())
        m = re.search(r"(_[a-z_]*lock[a-z_]*)", sup.reason)
        lock = m.group(1) if m else None
        if lock is None and len(declared) == 1:
            lock = next(iter(declared))
        if lock is not None:
            out[info.key] = lock
    return out


@rule(
    "lock-order",
    "The lock-acquisition graph over the runtime must be acyclic "
    "(a cycle is a deadlock two threads can reach), no blocking call "
    "(queue waits, thread join/start, future result, host_fetch, "
    "sleep, fsync) may run while a lock is held — another thread may "
    "need that lock to make the blocking operation complete — and a "
    "helper documented 'caller holds <lock>' must actually be called "
    "with the lock held at every resolved call site. Interprocedural: "
    "held locks propagate through the call graph and findings carry "
    "the call path.")
def lock_order(modules: List[Module]) -> Iterator[Finding]:
    graph = _call_graph(modules)
    cfg = dataflow.LockConfig(
        declared=_declared_locks(modules),
        blocking_attrs=LOCK_BLOCKING_ATTRS,
        blocking_dotted=LOCK_BLOCKING_DOTTED,
        blocking_funcs=LOCK_BLOCKING_FUNCS,
    )
    report = dataflow.run_locks(graph, cfg)

    # (a) deadlock proof: the acquisition graph must be acyclic.
    for cycle in dataflow.find_lock_cycles(report.edges):
        ring = cycle + cycle[:1]
        witness_rel, witness_line, _ = report.edges[(ring[0], ring[1])]
        yield Finding(
            "lock-order", witness_rel, witness_line,
            "lock-order cycle (deadlock reachable): " +
            " -> ".join(_lock_name(l) for l in ring) +
            " — two threads taking these locks in opposite orders wait "
            "on each other forever; impose one global order")

    # (b) blocking while holding a lock.
    for rel, line, held, site in sorted(
            report.blocking, key=lambda b: (b[0], b[1], b[3].desc)):
        path = (" via " + " -> ".join(site.path)) if site.path else ""
        yield Finding(
            "lock-order", rel, line,
            f"blocking operation {site.desc} while holding "
            f"{_lock_name(held)}{path} — a thread that needs this lock "
            f"to let the operation complete deadlocks (and every other "
            f"contender stalls for the operation's full duration); move "
            f"the wait outside the critical section")

    # (c) caller-holds-lock helpers: verify every resolved call site.
    helpers = _caller_holds_helpers(graph)
    if helpers:
        held_at: Dict[Tuple[str, str],
                      List[Tuple[str, int, Set[str]]]] = {}
        engine = dataflow._LockEngine(graph, cfg)
        for info in graph.iter_functions():
            mod = graph.modules[info.rel]

            def on_call(call, held, info=info, mod=mod):
                callee = graph.resolve_call(mod, call, info)
                if callee is not None and callee.key in helpers:
                    held_at.setdefault(callee.key, []).append(
                        (info.rel, call.lineno,
                         {lock[2] for lock in held}))

            engine._walk(info, on_call, lambda *a: None)
        for key, lock in sorted(helpers.items()):
            for rel, line, held_names in held_at.get(key, []):
                if lock not in held_names:
                    yield Finding(
                        "lock-order", rel, line,
                        f"{key[1]} is documented 'caller holds "
                        f"{lock}' but this call site does not hold it — "
                        f"the helper touches guarded state unlocked")


# ---------------------------------------------------------------------------
# (10) budget-flow
# ---------------------------------------------------------------------------

_BUDGET_REL = "pipelinedp_tpu/budget_accounting.py"
_DP_COMPUTATIONS_REL = "pipelinedp_tpu/dp_computations.py"

# Noise-mechanism constructors: only dp_computations may build them (and
# only from a registered MechanismSpec, via create_additive_mechanism /
# create_mean_mechanism).
_MECHANISM_CONSTRUCTORS = frozenset({
    "LaplaceMechanism", "GaussianMechanism",
})
_MECHANISM_FACTORY_ATTRS = frozenset({
    "create_from_epsilon", "create_from_epsilon_delta",
    "create_from_std_deviation",
})


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _register_calls_referencing(stmts: Iterable[ast.stmt],
                                var: str) -> bool:
    """True when some statement calls *_register_mechanism(...) with
    `var` reachable in its arguments (MechanismSpecInternal wrapping
    included)."""
    for stmt in stmts:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            leaf = func.attr if isinstance(func, ast.Attribute) else \
                (func.id if isinstance(func, ast.Name) else "")
            if leaf != "_register_mechanism":
                continue
            for arg in list(node.args) + [kw.value
                                          for kw in node.keywords]:
                if var in _names_in(arg):
                    return True
    return False


@rule(
    "budget-flow",
    "Every constructed MechanismSpec must reach BudgetAccountant."
    "_register_mechanism on all paths (the static dual of the runtime "
    "no_new_mechanisms guard): specs may only be constructed in "
    "budget_accounting.py and must be registered in the same suite "
    "before any return; noise mechanisms (Laplace/Gaussian) may only be "
    "built inside dp_computations.py from a registered spec; "
    "_register_mechanism may only be called from request_budget "
    "(graph-build time); and a request_budget() result must be bound — "
    "a discarded spec is budget spent on noise nobody can calibrate.")
def budget_flow(modules: List[Module]) -> Iterator[Finding]:
    graph = _call_graph(modules)
    for info in graph.iter_functions():
        mod = graph.modules[info.rel]
        fn = info.node
        # (1) + (2): MechanismSpec construction siting + registration.
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            dotted = mod.dotted(node.func) or ""
            leaf = dotted.rsplit(".", 1)[-1]
            if leaf == "MechanismSpec" and (
                    dotted == "MechanismSpec" or
                    dotted.endswith("budget_accounting.MechanismSpec") or
                    ".MechanismSpec" in dotted):
                if info.rel != _BUDGET_REL:
                    yield Finding(
                        "budget-flow", info.rel, node.lineno,
                        "MechanismSpec constructed outside "
                        "budget_accounting.py — specs exist only as "
                        "receipts of BudgetAccountant.request_budget, "
                        "which registers them with the ledger; an "
                        "ad-hoc spec is unaccounted noise")
            # (3): direct mechanism construction outside dp_computations.
            ctor = leaf if leaf in _MECHANISM_CONSTRUCTORS else None
            factory = (node.func.attr
                       if isinstance(node.func, ast.Attribute) and
                       node.func.attr in _MECHANISM_FACTORY_ATTRS
                       else None)
            if (ctor or factory) and info.rel not in (
                    _DP_COMPUTATIONS_REL,):
                what = ctor or factory
                yield Finding(
                    "budget-flow", info.rel, node.lineno,
                    f"noise mechanism built directly ({what}) outside "
                    f"dp_computations.py — mechanisms must be created "
                    f"by create_additive_mechanism/create_mean_mechanism "
                    f"from a MechanismSpec the ledger registered, or "
                    f"the noise it draws is outside every privacy proof")
        # Registration-dominance inside budget_accounting.py.
        if info.rel == _BUDGET_REL:
            yield from _check_spec_registration(mod, info)
        # (4): discarded request_budget results. Only the ACCOUNTANT's
        # request_budget returns the spec receipt; a combiner's
        # same-named hook stores its spec itself and returns None.
        for node in ast.walk(fn):
            if isinstance(node, ast.Expr) and \
                    isinstance(node.value, ast.Call):
                call = node.value
                leaf = (call.func.attr
                        if isinstance(call.func, ast.Attribute)
                        else (call.func.id
                              if isinstance(call.func, ast.Name)
                              else ""))
                resolved = graph.resolve_call(mod, call, info)
                dotted = mod.dotted(call.func) or ""
                accountant_recv = "accountant" in \
                    dotted.rsplit(".", 1)[0].lower()
                if leaf == "request_budget" and (
                        accountant_recv or
                        (resolved is not None and
                         resolved.rel == _BUDGET_REL)):
                    yield Finding(
                        "budget-flow", info.rel, node.lineno,
                        "request_budget() result discarded — the ledger "
                        "registered (and will spend) budget for a "
                        "mechanism whose spec nobody holds, so its noise "
                        "can never be calibrated; bind the returned "
                        "MechanismSpec or drop the request")
        # (5): _register_mechanism called outside request_budget.
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            leaf = (node.func.attr
                    if isinstance(node.func, ast.Attribute)
                    else (node.func.id
                          if isinstance(node.func, ast.Name) else ""))
            if leaf != "_register_mechanism":
                continue
            if info.rel == _BUDGET_REL and info.name in (
                    "request_budget", "_register_mechanism"):
                continue
            yield Finding(
                "budget-flow", info.rel, node.lineno,
                f"_register_mechanism called from {info.qualname} — "
                f"registration belongs to request_budget (graph-build "
                f"time) only; any other caller is the static shape of "
                f"the double-spend no_new_mechanisms guards against")


def _check_spec_registration(mod: Module,
                             info) -> Iterator[Finding]:
    """Within budget_accounting.py: a `x = MechanismSpec(...)` must be
    followed, in the same statement suite, by a _register_mechanism call
    referencing x."""
    def suites(node: ast.AST) -> Iterator[List[ast.stmt]]:
        for child in ast.walk(node):
            for field in ("body", "orelse", "finalbody"):
                stmts = getattr(child, field, None)
                if isinstance(stmts, list) and stmts and \
                        isinstance(stmts[0], ast.stmt):
                    yield stmts

    for suite in suites(info.node):
        for i, stmt in enumerate(suite):
            if not (isinstance(stmt, ast.Assign) and
                    isinstance(stmt.value, ast.Call)):
                continue
            dotted = mod.dotted(stmt.value.func) or ""
            if dotted.rsplit(".", 1)[-1] != "MechanismSpec":
                continue
            targets = [t.id for t in stmt.targets
                       if isinstance(t, ast.Name)]
            if not targets:
                continue
            var = targets[0]
            if not _register_calls_referencing(suite[i + 1:], var):
                yield Finding(
                    "budget-flow", mod.rel, stmt.lineno,
                    f"MechanismSpec bound to {var!r} is never passed to "
                    f"_register_mechanism in this suite — a spec that "
                    f"skips the ledger is noise outside the privacy "
                    f"proof; register it (or construct it inside the "
                    f"_register_mechanism call)")


# ---------------------------------------------------------------------------
# (11) thread-escape
# ---------------------------------------------------------------------------


def _loc_desc(loc: Tuple[str, str, str]) -> str:
    rel, cls, name = loc
    return f"self.{name} ({rel}:{cls})" if cls else \
        f"module global {name!r} ({rel})"


@rule(
    "thread-escape",
    "No shared mutable state between thread roots without a common "
    "lock. Thread roots are discovered structurally "
    "(threading.Thread(target=)/Timer, ThreadPoolExecutor.submit/map, "
    "BaseHTTPRequestHandler subclasses, __main__ subprocess entries); "
    "module globals and self.-attributes written from two roots — or "
    "written from one and read from another — where some cross-root "
    "access pair holds no common lock are races, reported with both "
    "root->access call paths. queue/Event/Lock/local state, "
    "immutable-after-__init__ attributes and _GUARDED_BY-declared "
    "attributes (lock-discipline's territory) are declassified "
    "structurally. Consistently-locked-but-undeclared locations get a "
    "fix-it naming the _GUARDED_BY declaration to add.")
def thread_escape(modules: List[Module]) -> Iterator[Finding]:
    graph = _call_graph(modules)
    report = threads_mod.run_threads(graph, _declared_locks(modules),
                                     _declared_guarded_attrs(modules))
    for race in report.races:
        desc = _loc_desc(race.loc)
        if race.kind == "guard-candidate":
            yield Finding(
                "thread-escape", race.rel, race.line,
                f"{desc} is shared across thread roots and every access "
                f"holds {race.candidate_lock!r}, but the attribute is "
                f"not declared — add _GUARDED_BY = guarded_by("
                f"{race.candidate_lock!r}, {race.loc[2]!r}) so the "
                f"lock-discipline rule enforces it from now on. "
                f"Roots: {race.a.root.describe()} and "
                f"{race.b.root.describe()}")
            continue
        fixit = ""
        if race.candidate_lock is not None:
            fixit = (f"; other accesses hold {race.candidate_lock!r} — "
                     f"declare _GUARDED_BY = guarded_by("
                     f"{race.candidate_lock!r}, {race.loc[2]!r}) and "
                     f"take it here")
        yield Finding(
            "thread-escape", race.rel, race.line,
            f"{race.kind} race: {desc} is accessed from two thread "
            f"roots with no common lock{fixit}. "
            f"Path A: {race.a.render()}. Path B: {race.b.render()}")


# ---------------------------------------------------------------------------
# (12) determinism
# ---------------------------------------------------------------------------

# Iteration-order sources: their result's ORDER is not stable across
# processes/runs (set/frozenset iteration under hash randomization,
# directory listings, object identity). Matched by exact canonical
# dotted name (an `ev.set()` never matches bare "set").
DETERMINISM_SOURCES: Dict[str, str] = {
    "set": "set() iteration order",
    "frozenset": "frozenset() iteration order",
    "os.listdir": "os.listdir() order",
    "os.scandir": "os.scandir() order",
    "glob.glob": "glob.glob() order",
    "glob.iglob": "glob.iglob() order",
    "id": "id() value",
}

# Order-insensitive reductions and explicit-ordering constructs clear
# order taint: sorted() IS the sanctioned fix.
DETERMINISM_DECLASS_CALLS = frozenset({
    "sorted", "len", "min", "max", "sum", "any", "all", "bool",
    "isinstance", "hasattr", "range",
    # Sorted-output uniques (numpy/jax sort; pandas.unique does NOT and
    # deliberately has no entry here).
    "numpy.unique", "jax.numpy.unique",
})
DETERMINISM_SANITIZER_ATTRS = frozenset({"sort"})


def _determinism_sink_args(graph, mod, scope, call, callee):
    """Sink detector for the determinism rule: flows whose ORDER is the
    released/persisted/derived artifact."""
    hits = []
    dotted = mod.dotted(call.func) or ""
    leaf = dotted.rsplit(".", 1)[-1]
    attr = call.func.attr if isinstance(call.func, ast.Attribute) else None
    kw_exprs = [kw.value for kw in call.keywords]
    if leaf == "fold_in" and call.args:
        # jax.random.fold_in(key, data): `data` selects the noise
        # stream — an order-dependent value here forks the release.
        hits.append(("fold_in noise-key derivation",
                     list(call.args[1:]) + kw_exprs))
    elif leaf == "make_noise_key":
        hits.append(("noise-key derivation", list(call.args) + kw_exprs))
    elif attr == "put" and len(call.args) == 3:
        # BlockJournal.put(job_id, key, record): the journal KEY —
        # resume-time addressing must be reproducible.
        hits.append(("journal key", [call.args[1]]))
    elif leaf == "record_mechanism":
        # Odometer records must append in a reproducible order, or the
        # ledger's bit-exact left-to-right eps fold diverges on replay.
        hits.append(("odometer record", list(call.args) + kw_exprs))
    return hits


@rule(
    "determinism",
    "Bit-identical releases require order-deterministic flows: values "
    "whose ORDER comes from set()/frozenset iteration, os.listdir/glob "
    "listings or id() must not reach a release sink (the drivers' "
    "released values), a journal key, a fold_in/noise-key derivation "
    "or an odometer record. sorted(...) (and order-insensitive "
    "reductions: len/min/max/sum/any/all) sanitize. Interprocedural: "
    "findings carry the full source->sink call path.")
def determinism(modules: List[Module]) -> Iterator[Finding]:
    graph = _call_graph(modules)
    cfg = dataflow.TaintConfig(
        sources={},
        sanitizers=set(),
        sanitizer_attrs=DETERMINISM_SANITIZER_ATTRS,
        sanitizer_dotted=frozenset(),
        declass_calls=DETERMINISM_DECLASS_CALLS,
        declass_attrs=frozenset({"shape", "ndim", "size", "nbytes",
                                 "dtype", "itemsize"}),
        release_funcs=TAINT_RELEASE_FUNCS,
        sink_args=_determinism_sink_args,
        source_calls=DETERMINISM_SOURCES,
        literal_set_label="set-literal iteration order",
    )
    for f in sorted(dataflow.run_taint(graph, cfg),
                    key=lambda f: (f.rel, f.line, f.sink,
                                   f.origin.label)):
        yield Finding(
            "determinism", f.rel, f.line,
            f"iteration-order-dependent value reaches {f.sink} — the "
            f"order is not stable across processes/restarts, so a "
            f"resumed or retried job would replay a DIFFERENT release; "
            f"sort the flow (sorted(...)) or suppress with a reason "
            f"proving the order cannot vary. Path: "
            f"{f.origin.render_path()} -> {f.sink} ({f.rel}:{f.line})")
