"""AST rules enforcing the repo's determinism & jax-purity contracts.

Each rule is a function ``(tree, ctx) -> list[Violation]`` registered in
``RULES``.  Rules are deliberately *syntactic*: they encode the repo's own
coding contracts (sorted iteration, seeded PRNGs, pure jit bodies, the
hot-loop ban list) rather than attempting whole-program dataflow.  Where a
construct is deliberate, the author annotates it in place with
``# repro-lint: allow(<rule>)`` and the justification survives review.

Rule inventory
--------------

``unseeded-random``
    Module-level global-PRNG calls (``np.random.rand``, ``random.choice``)
    and seedless generator construction (``default_rng()``, ``Philox()``,
    ``random.Random()``).  Replays are only deterministic if every stream
    has an explicit seed.

``iter-order``
    Iterating a ``set``/``frozenset`` (or a dict-of-sets entry) where the
    order can leak into results: ``for`` loops, comprehensions, and
    order-sensitive reductions (``sum``/``min``/``max``/``list``/``tuple``).
    String hashing is salted per process (PYTHONHASHSEED), so set order is
    *not* reproducible across runs — float accumulation or placement order
    fed from it silently breaks bit-equality.  ``sorted(...)`` launders;
    order-free reductions (``len``/``any``/``all``/set algebra) are exempt.

``float-sum``
    Builtin ``sum()`` applied directly to an array-like value.  Builtin sum
    accumulates left-to-right in object space; zone code must use
    ``ndarray.sum()``/``math.fsum`` so accumulation dtype and order are
    explicit (and match the jax path).

``np-reduce-dtype``
    ``np.sum``/``np.dot``/``np.mean``/... function-form reductions without a
    pinned ``dtype``.  The accumulator dtype must be explicit (float64) in
    zone files — backend golden-equality rests on both paths reducing in
    float64.

``float32-literal``
    float32/float16/bfloat16 dtypes in arena/search array constructors.  The
    search stack's exactness arguments (dyadic grids, exact segment-sums)
    are float64-only.

``jax-purity``
    Python side effects inside traced code: ``print``, ``np.*`` calls, and
    mutation of closed-over state inside functions that are jit/vmap/scan
    bodies.  Tracing executes such code once at trace time — silent
    wrong-results territory.

``x64-scope``
    ``jax.config.update``, ``jax.enable_x64(...)`` calls, or an
    ``enable_x64`` import outside the one scoped helper
    (``search/backend.py``).  A process-wide x64 flip would poison the
    float32 Pallas kernels; the scoped context is the only sanctioned way.

``hot-loop``
    ``copy.deepcopy``, libm transcendentals (``exp``/``log``/trig — not
    correctly rounded, platform-varying), and wall-clock reads inside the
    engine/search step paths.  The annealer's accept decisions must compare
    exact quantities, bit-identical across backends and platforms.

``pallas-interpret``
    ``interpret=True`` hardcoded at a call site in the kernel zone.  The
    interpreter is the golden-oracle *test* harness; committed call sites
    must plumb the flag (``default_interpret()`` / a parameter) so the
    compiled kernel actually runs on TPU.

``pallas-accum-order``
    Augmented assignment onto a ``Ref`` slot whose statement depends on
    ``pl.program_id`` — cross-program float accumulation order is a grid
    execution detail, not IEEE semantics.  Kernels must accumulate into
    their own output block (or carry exact grid-quantized values, where
    order provably cannot matter).

``pallas-accum-dtype``
    ``zeros``/``ones``/``empty``/``full`` accumulator constructors in the
    golden-oracle kernel zone without an explicit wide dtype.  ``jnp``
    defaults to float32 outside an x64 scope, silently breaking the
    bit-equality contract with the float64 oracles.

``pallas-grid-truncate``
    ``pallas_call`` grids computed with floor division (``B // block``) —
    a batch that is not a block multiple silently drops its tail.  Use
    ``pl.cdiv`` with host-side padding (and slice the outputs) instead.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple


@dataclasses.dataclass(frozen=True, order=True)
class Violation:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}"


@dataclasses.dataclass
class RuleContext:
    """Per-file facts rules need: path, zone knowledge, source lines."""

    path: str
    set_attrs: Tuple[str, ...] = ()
    x64_exempt: bool = False


RULES: Dict[str, Callable[[ast.AST, RuleContext], List[Violation]]] = {}


def _rule(name: str):
    def wrap(fn):
        RULES[name] = fn
        return fn

    return wrap


def _v(ctx: RuleContext, node: ast.AST, rule: str, message: str) -> Violation:
    return Violation(
        path=ctx.path,
        line=getattr(node, "lineno", 1),
        col=getattr(node, "col_offset", 0),
        rule=rule,
        message=message,
    )


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# --------------------------------------------------------------------------
# unseeded-random
# --------------------------------------------------------------------------

#: numpy module-level convenience functions that draw from the hidden
#: global RandomState.
_NP_GLOBAL_FNS = {
    "rand", "randn", "randint", "random", "random_sample", "ranf", "sample",
    "choice", "shuffle", "permutation", "uniform", "normal",
    "standard_normal", "exponential", "poisson", "beta", "gamma", "seed",
    "bytes", "random_integers",
}

#: stdlib ``random`` module-level functions (the hidden global Random()).
_PY_GLOBAL_FNS = {
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "normalvariate", "betavariate",
    "expovariate", "triangular", "getrandbits", "seed", "vonmisesvariate",
    "paretovariate", "weibullvariate", "lognormvariate",
}

#: Constructors that take the seed as their first argument.
_SEEDED_CTORS = {
    "default_rng", "Philox", "PCG64", "PCG64DXSM", "MT19937", "SFC64",
    "SeedSequence", "RandomState", "Random",
}


@_rule("unseeded-random")
def _check_unseeded_random(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted is None:
            continue
        parts = dotted.split(".")
        head, tail = parts[0], parts[-1]
        # np.random.rand(...) / numpy.random.shuffle(...)
        if (
            len(parts) == 3
            and head in ("np", "numpy")
            and parts[1] == "random"
            and tail in _NP_GLOBAL_FNS
        ):
            out.append(
                _v(
                    ctx, node, "unseeded-random",
                    f"`{dotted}` draws from numpy's hidden global RandomState; "
                    "construct a seeded Generator "
                    "(np.random.Generator(np.random.Philox(seed)))",
                )
            )
            continue
        # random.choice(...) — the stdlib hidden global Random().
        if len(parts) == 2 and head == "random" and tail in _PY_GLOBAL_FNS:
            out.append(
                _v(
                    ctx, node, "unseeded-random",
                    f"`{dotted}` uses the process-global random.Random(); "
                    "pass an explicitly seeded random.Random(seed) instead",
                )
            )
            continue
        # default_rng() / np.random.Philox() / random.Random() without a seed.
        if tail in _SEEDED_CTORS and not node.args:
            seed_kw = {"seed", "x", "entropy"}
            if not any(kw.arg in seed_kw for kw in node.keywords):
                out.append(
                    _v(
                        ctx, node, "unseeded-random",
                        f"`{dotted}()` without a seed is entropy-seeded; "
                        "every PRNG in a deterministic zone takes an explicit "
                        "seed",
                    )
                )
    return out


# --------------------------------------------------------------------------
# iter-order
# --------------------------------------------------------------------------

_SET_RETURNING_METHODS = {
    "union", "intersection", "difference", "symmetric_difference", "copy",
}
_SET_OPS = (ast.BitOr, ast.BitAnd, ast.Sub, ast.BitXor)
_ORDER_SENSITIVE_BUILTINS = {"sum", "list", "tuple", "max", "min", "next", "iter"}
#: Consumers that launder iteration order: sorting imposes one, set/frozenset
#: construction erases it, any/all/len never expose it.
_ORDER_FREE_CONSUMERS = {"sorted", "set", "frozenset", "any", "all", "len"}


class _SetTracker(ast.NodeVisitor):
    """Scope-aware tracking of set-typed names and dict-of-set names.

    Intentionally simple: statement-order single pass per scope, names
    resolved through the lexical scope stack.  ``kind`` is ``"set"`` or
    ``"dictofsets"``.
    """

    def __init__(self, ctx: RuleContext):
        self.ctx = ctx
        self.scopes: List[Dict[str, str]] = [{}]
        self.out: List[Violation] = []
        # Comprehension nodes consumed directly by an order-free builtin
        # (sorted/set/frozenset/any/all/len) — their generators may iterate
        # sets freely, the consumer erases or imposes the order.
        self._laundered: Set[int] = set()
        # Attribute names from ctx.set_attrs that this module assigns a
        # non-set value to on `self` (e.g. PlacementArena's sorted-list
        # `self.dims` vs ResourceVector's frozenset property of the same
        # name).  Local assignment evidence beats the zone-wide default.
        self._self_nonset: Set[str] = set()

    def preanalyze(self, tree: ast.AST) -> None:
        """Collect module-level `self.<attr> = ...` typing evidence."""
        set_assigned: Set[str] = set()
        nonset_assigned: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            for t in targets:
                if (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                    and t.attr in self.ctx.set_attrs
                ):
                    bucket = (
                        set_assigned
                        if self._kind(value) == "set"
                        else nonset_assigned
                    )
                    bucket.add(t.attr)
        self._self_nonset = nonset_assigned - set_assigned

    # -- type inference ----------------------------------------------------
    def _lookup(self, name: str) -> Optional[str]:
        for scope in reversed(self.scopes):
            if name in scope:
                return scope[name]
        return None

    def _kind(self, node: ast.AST) -> Optional[str]:
        """'set' / 'dictofsets' / None for an expression."""
        if isinstance(node, (ast.Set, ast.SetComp)):
            return "set"
        if isinstance(node, ast.Name):
            return self._lookup(node.id)
        if isinstance(node, ast.Attribute):
            if node.attr in self.ctx.set_attrs:
                if (
                    isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                    and node.attr in self._self_nonset
                ):
                    return None
                return "set"
            return None
        if isinstance(node, ast.IfExp):
            return self._kind(node.body) or self._kind(node.orelse)
        if isinstance(node, ast.BinOp) and isinstance(node.op, _SET_OPS):
            if self._kind(node.left) == "set" or self._kind(node.right) == "set":
                return "set"
            return None
        if isinstance(node, ast.Subscript):
            if self._kind(node.value) == "dictofsets":
                return "set"
            return None
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id in ("set", "frozenset"):
                return "set"
            if isinstance(f, ast.Attribute):
                base = self._kind(f.value)
                if base == "set" and f.attr in _SET_RETURNING_METHODS:
                    return "set"
                if base == "dictofsets" and f.attr == "get":
                    return "set"
            return None
        if isinstance(node, ast.DictComp):
            if self._kind(node.value) == "set":
                return "dictofsets"
            return None
        if isinstance(node, ast.Dict):
            if node.values and all(self._kind(v) == "set" for v in node.values):
                return "dictofsets"
            return None
        return None

    def _bind(self, target: ast.AST, kind: Optional[str]) -> None:
        if isinstance(target, ast.Name):
            if kind is not None:
                self.scopes[-1][target.id] = kind
            else:
                self.scopes[-1].pop(target.id, None)

    # -- scope plumbing ----------------------------------------------------
    def _visit_function(self, node) -> None:
        self.scopes.append({})
        for stmt in node.body:
            self.visit(stmt)
        self.scopes.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function
    visit_ClassDef = _visit_function
    visit_Lambda = lambda self, node: self.generic_visit(node)  # noqa: E731

    # -- assignments -------------------------------------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        kind = self._kind(node.value)
        for t in node.targets:
            self._bind(t, kind)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self.generic_visit(node)
        if node.value is not None:
            self._bind(node.target, self._kind(node.value))

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.generic_visit(node)
        # `s |= set(...)` keeps s a set; anything else drops tracking.
        if isinstance(node.target, ast.Name):
            cur = self._lookup(node.target.id)
            if cur == "set" and not isinstance(node.op, _SET_OPS):
                self._bind(node.target, None)

    # -- flag sites --------------------------------------------------------
    def _flag(self, node: ast.AST, what: str) -> None:
        self.out.append(
            _v(
                self.ctx, node, "iter-order",
                f"{what} iterates a set — iteration order depends on "
                "PYTHONHASHSEED; wrap in sorted(...) or restructure",
            )
        )

    def visit_For(self, node: ast.For) -> None:
        if self._kind(node.iter) == "set":
            self._flag(node.iter, "for-loop")
        self.generic_visit(node)

    def _visit_comp(self, node) -> None:
        if id(node) not in self._laundered:
            for gen in node.generators:
                if self._kind(gen.iter) == "set":
                    self._flag(gen.iter, "comprehension")
        self.generic_visit(node)

    visit_ListComp = _visit_comp
    visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    def visit_SetComp(self, node: ast.SetComp) -> None:
        # Building a set erases iteration order — never a hazard by itself.
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Name) and f.id in _ORDER_FREE_CONSUMERS:
            for arg in node.args:
                if isinstance(
                    arg, (ast.GeneratorExp, ast.ListComp, ast.SetComp)
                ):
                    self._laundered.add(id(arg))
        if (
            isinstance(f, ast.Name)
            and f.id in _ORDER_SENSITIVE_BUILTINS
            and node.args
            and self._kind(node.args[0]) == "set"
        ):
            self._flag(node, f"{f.id}()")
        self.generic_visit(node)


@_rule("iter-order")
def _check_iter_order(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    tracker = _SetTracker(ctx)
    tracker.preanalyze(tree)
    tracker.visit(tree)
    return tracker.out


# --------------------------------------------------------------------------
# float-sum / np-reduce-dtype / float32-literal
# --------------------------------------------------------------------------


@_rule("float-sum")
def _check_float_sum(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "sum"
            and node.args
            and isinstance(
                node.args[0], (ast.Name, ast.Attribute, ast.Subscript)
            )
        ):
            out.append(
                _v(
                    ctx, node, "float-sum",
                    "builtin sum() over an array-like accumulates "
                    "left-to-right in object space; use ndarray.sum() "
                    "(explicit dtype) or math.fsum",
                )
            )
    return out


_NP_REDUCTIONS = {"sum", "dot", "matmul", "mean", "cumsum", "prod", "average"}


@_rule("np-reduce-dtype")
def _check_np_reduce_dtype(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted is None:
            continue
        parts = dotted.split(".")
        if (
            len(parts) == 2
            and parts[0] in ("np", "numpy")
            and parts[1] in _NP_REDUCTIONS
            and not any(kw.arg == "dtype" for kw in node.keywords)
        ):
            out.append(
                _v(
                    ctx, node, "np-reduce-dtype",
                    f"`{dotted}` without a pinned dtype — zone reductions "
                    "must accumulate in float64 (pass dtype=np.float64 or "
                    "cast the operands)",
                )
            )
    return out


_NARROW_DTYPES = {"float32", "float16", "bfloat16"}


@_rule("float32-literal")
def _check_float32_literal(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in _NARROW_DTYPES:
            root = _dotted(node)
            if root and root.split(".")[0] in ("np", "numpy", "jnp", "jax"):
                out.append(
                    _v(
                        ctx, node, "float32-literal",
                        f"`{root}` in an exactness zone — the search stack's "
                        "bit-equality arguments are float64-only",
                    )
                )
        elif (
            isinstance(node, ast.Call)
            and any(
                kw.arg == "dtype"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value in _NARROW_DTYPES
                for kw in node.keywords
            )
        ):
            out.append(
                _v(
                    ctx, node, "float32-literal",
                    "narrow dtype string in an exactness zone — the search "
                    "stack's bit-equality arguments are float64-only",
                )
            )
    return out


# --------------------------------------------------------------------------
# jax-purity / x64-scope
# --------------------------------------------------------------------------

_TRACERS = {"jit", "vmap", "pmap", "grad", "value_and_grad", "scan", "checkpoint"}
_MUTATING_METHODS = {
    "append", "extend", "insert", "add", "update", "setdefault", "pop",
    "popitem", "remove", "discard", "clear", "sort", "reverse",
}


def _is_tracer_expr(node: ast.AST) -> bool:
    """True for `jit`, `jax.jit`, `jax.lax.scan`, `functools.partial(jax.jit, ...)`."""
    dotted = _dotted(node)
    if dotted is not None:
        return dotted.split(".")[-1] in _TRACERS
    if isinstance(node, ast.Call):  # partial(jax.jit, ...) decorator form
        f = _dotted(node.func)
        if f and f.split(".")[-1] == "partial" and node.args:
            return _is_tracer_expr(node.args[0])
    return False


class _TracedCollector(ast.NodeVisitor):
    """Find FunctionDefs that are (or are nested in) jit/vmap/scan bodies."""

    def __init__(self):
        self.traced: List[ast.FunctionDef] = []
        self._defs: List[ast.FunctionDef] = []  # all defs, for name lookup

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._defs.append(node)
        if any(_is_tracer_expr(d) for d in node.decorator_list):
            self.traced.append(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call) -> None:
        # jax.jit(f) / jax.vmap(f) / jax.lax.scan(f, ...) with a local f.
        if _is_tracer_expr(node.func) and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Name):
                for d in self._defs:
                    if d.name == arg.id and d not in self.traced:
                        self.traced.append(d)
        self.generic_visit(node)


def _local_names(fn: ast.FunctionDef) -> set:
    """Names bound inside ``fn`` (params + any Name store), nested defs
    included — good enough to tell closed-over state from locals."""
    bound = set()
    a = fn.args
    for p in (
        list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        + ([a.vararg] if a.vararg else []) + ([a.kwarg] if a.kwarg else [])
    ):
        bound.add(p.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            bound.add(node.name)
    return bound


@_rule("jax-purity")
def _check_jax_purity(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    collector = _TracedCollector()
    collector.visit(tree)
    out: List[Violation] = []
    seen: set = set()
    for fn in collector.traced:
        bound = _local_names(fn)
        for node in ast.walk(fn):
            key = (id(node),)
            if key in seen:
                continue
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if dotted == "print" or (
                    isinstance(node.func, ast.Name) and node.func.id == "print"
                ):
                    seen.add(key)
                    out.append(
                        _v(
                            ctx, node, "jax-purity",
                            "print() inside a traced function runs once at "
                            "trace time; use jax.debug.print or hoist it",
                        )
                    )
                elif dotted and dotted.split(".")[0] in ("np", "numpy"):
                    seen.add(key)
                    out.append(
                        _v(
                            ctx, node, "jax-purity",
                            f"`{dotted}` inside a traced function executes at "
                            "trace time on abstract values; use jnp/lax "
                            "equivalents",
                        )
                    )
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MUTATING_METHODS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id not in bound
                ):
                    seen.add(key)
                    out.append(
                        _v(
                            ctx, node, "jax-purity",
                            f"`{node.func.value.id}.{node.func.attr}(...)` "
                            "mutates closed-over state inside a traced "
                            "function — a trace-time side effect",
                        )
                    )
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                for t in targets:
                    if (
                        isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id not in bound
                    ):
                        seen.add(key)
                        out.append(
                            _v(
                                ctx, node, "jax-purity",
                                f"subscript-assign to closed-over "
                                f"`{t.value.id}` inside a traced function — "
                                "a trace-time side effect",
                            )
                        )
    return out


@_rule("x64-scope")
def _check_x64_scope(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    if ctx.x64_exempt:
        return []
    out: List[Violation] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func)
            if dotted in ("jax.config.update", "config.update"):
                out.append(
                    _v(
                        ctx, node, "x64-scope",
                        "`jax.config.update` outside search/backend.py — "
                        "process-wide config flips poison the float32 "
                        "kernels; use backend.x64()",
                    )
                )
            elif dotted is not None and dotted.split(".")[-1] == "enable_x64":
                out.append(
                    _v(
                        ctx, node, "x64-scope",
                        f"`{dotted}(...)` outside search/backend.py; "
                        "use the scoped backend.x64() helper",
                    )
                )
        elif isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name == "enable_x64":
                    out.append(
                        _v(
                            ctx, node, "x64-scope",
                            "`enable_x64` imported outside search/backend.py; "
                            "use the scoped backend.x64() helper",
                        )
                    )
    return out


# --------------------------------------------------------------------------
# hot-loop
# --------------------------------------------------------------------------

_TRANSCENDENTALS = {
    "exp", "expm1", "exp2", "log", "log1p", "log2", "log10", "power", "pow",
    "sin", "cos", "tan", "sinh", "cosh", "tanh", "arcsin", "arccos",
    "arctan", "arctan2", "asin", "acos", "atan", "atan2",
}
_CLOCK_FNS = {
    "time.time", "time.perf_counter", "time.monotonic", "time.process_time",
    "time.time_ns", "time.perf_counter_ns", "time.monotonic_ns",
    "datetime.now", "datetime.utcnow", "datetime.today",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.datetime.today", "date.today", "datetime.date.today",
}


@_rule("hot-loop")
def _check_hot_loop(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted is None:
            continue
        parts = dotted.split(".")
        if dotted in ("copy.deepcopy", "deepcopy"):
            out.append(
                _v(
                    ctx, node, "hot-loop",
                    "copy.deepcopy in an engine/search path — use the "
                    "arena's snapshot/rollback ledger",
                )
            )
        elif (
            len(parts) == 2
            and parts[0] in ("math", "np", "numpy", "jnp")
            and parts[1] in _TRANSCENDENTALS
        ):
            out.append(
                _v(
                    ctx, node, "hot-loop",
                    f"`{dotted}` in an engine/search path — libm "
                    "transcendentals are not correctly rounded and vary by "
                    "platform; hot-loop decisions must compare exact "
                    "quantities (threshold accepting, not Metropolis)",
                )
            )
        elif dotted in _CLOCK_FNS:
            out.append(
                _v(
                    ctx, node, "hot-loop",
                    f"`{dotted}` in an engine/search path — wall-clock reads "
                    "make replays timing-dependent",
                )
            )
    return out


# --------------------------------------------------------------------------
# pallas-interpret / pallas-accum-order / pallas-accum-dtype /
# pallas-grid-truncate
# --------------------------------------------------------------------------


@_rule("pallas-interpret")
def _check_pallas_interpret(tree: ast.AST, ctx: RuleContext) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if (
                kw.arg == "interpret"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value is True
            ):
                out.append(
                    _v(
                        ctx, node, "pallas-interpret",
                        "`interpret=True` hardcoded at a committed call site "
                        "— the interpreter is the golden-oracle test path; "
                        "plumb the flag (default_interpret() / a parameter) "
                        "so the compiled kernel runs on TPU",
                    )
                )
    return out


def _is_program_id_call(node: ast.AST) -> bool:
    if isinstance(node, ast.Call):
        dotted = _dotted(node.func)
        return bool(dotted) and dotted.split(".")[-1] == "program_id"
    return False


def _program_id_names(tree: ast.AST) -> Set[str]:
    """Names bound (directly or via arithmetic) to a pl.program_id result."""
    names: Set[str] = set()
    changed = True

    def tainted(expr: ast.AST) -> bool:
        return any(
            _is_program_id_call(sub)
            or (isinstance(sub, ast.Name) and sub.id in names)
            for sub in ast.walk(expr)
        )

    while changed:  # tiny fixpoint: `i = pl.program_id(0)`, `row = i * blk`
        changed = False
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and tainted(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id not in names:
                        names.add(t.id)
                        changed = True
    return names


@_rule("pallas-accum-order")
def _check_pallas_accum_order(
    tree: ast.AST, ctx: RuleContext
) -> List[Violation]:
    out: List[Violation] = []
    names = _program_id_names(tree)
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.AugAssign)
            and isinstance(node.target, ast.Subscript)
        ):
            continue
        if any(
            _is_program_id_call(sub)
            or (isinstance(sub, ast.Name) and sub.id in names)
            for sub in ast.walk(node)
        ):
            out.append(
                _v(
                    ctx, node, "pallas-accum-order",
                    "accumulation depends on pl.program_id — cross-program "
                    "float accumulation order is a grid execution detail; "
                    "accumulate into the program's own output block, or "
                    "carry exact grid-quantized values",
                )
            )
    return out


#: Accumulator constructors whose positional dtype slot varies.
_ACCUM_CTORS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2}
#: Wide dtypes the exactness contract allows accumulating in.
_WIDE_DTYPES = {"float64", "int32", "int64", "bool_", "bool", "intp", "uint32"}


def _dtype_name(node: ast.AST) -> Optional[str]:
    """'float64' for `np.float64` / `jnp.float64` / 'float64', else None."""
    if isinstance(node, ast.Attribute):
        dotted = _dotted(node)
        if dotted and dotted.split(".")[0] in ("np", "numpy", "jnp", "jax"):
            return dotted.split(".")[-1]
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


@_rule("pallas-accum-dtype")
def _check_pallas_accum_dtype(
    tree: ast.AST, ctx: RuleContext
) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted is None:
            continue
        parts = dotted.split(".")
        if len(parts) != 2 or parts[0] not in ("np", "numpy", "jnp"):
            continue
        if parts[1] not in _ACCUM_CTORS:
            continue
        dtype_node = None
        for kw in node.keywords:
            if kw.arg == "dtype":
                dtype_node = kw.value
        if dtype_node is None:
            slot = _ACCUM_CTORS[parts[1]]
            if len(node.args) > slot:
                dtype_node = node.args[slot]
        if dtype_node is None:
            out.append(
                _v(
                    ctx, node, "pallas-accum-dtype",
                    f"`{dotted}` without an explicit dtype in the "
                    "golden-oracle kernel zone — jnp defaults to float32 "
                    "outside an x64 scope; pin dtype=jnp.float64 (or an "
                    "exact integer dtype)",
                )
            )
            continue
        name = _dtype_name(dtype_node)
        if name is not None and name not in _WIDE_DTYPES:
            out.append(
                _v(
                    ctx, node, "pallas-accum-dtype",
                    f"`{dotted}` accumulator pinned to `{name}` — the "
                    "golden-oracle comparison contract is float64/exact-int "
                    "only",
                )
            )
    return out


@_rule("pallas-grid-truncate")
def _check_pallas_grid_truncate(
    tree: ast.AST, ctx: RuleContext
) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted is None or dotted.split(".")[-1] != "pallas_call":
            continue
        for kw in node.keywords:
            if kw.arg != "grid":
                continue
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.BinOp) and isinstance(
                    sub.op, ast.FloorDiv
                ):
                    out.append(
                        _v(
                            ctx, sub, "pallas-grid-truncate",
                            "floor division in a pallas_call grid silently "
                            "drops the tail block when the batch is not a "
                            "block multiple; use pl.cdiv and pad/mask the "
                            "boundary",
                        )
                    )
    return out
