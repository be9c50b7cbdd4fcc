"""JAX-aware lint rules (stdlib ``ast`` only).

R1 implicit-device-transfer: ``float()`` / ``int()`` / ``bool()`` /
   ``np.asarray()`` / ``np.array()`` applied to a jax-typed value, or any
   ``.item()`` call, inside the configured hot-loop modules. Each of these
   blocks the Python thread on a device->host round trip — measured at
   ~100 ms+ through a remote-accelerator link — and none of them announce
   themselves. The fix is to keep the value on device, or to fetch
   explicitly through ``utils.transfer.logged_fetch`` (counted by obs and
   permitted by the runtime transfer guard).

R2 recompile-hazard: inside a ``@jax.jit`` function, a Python ``if`` /
   ``while`` on a tracer-typed name (a ConcretizationTypeError at best, a
   silent per-value recompile with hashable scalars at worst), an f-string
   formatting a tracer, and malformed ``static_argnums`` / ``static_argnames``
   (non-literal values, names that match no parameter, or parameters
   annotated as arrays — array-valued statics recompile on every distinct
   value).

R3 dtype-discipline: hardcoded ``4`` / ``8`` itemsize multipliers in
   byte-accounting code (the PR-1 HBM-budget bug class: an x64 dataset
   under-counted by 2x), ``np.float32(...)`` casts and
   ``.astype(np.float32)`` where the dtype should be derived from the data,
   and — in the configured dtype-strict modules — ``jnp.array(...)`` /
   ``jnp.asarray(...)`` without an explicit dtype (silently picks f32 or
   weak-types by backend default).

R4 swallow-and-continue: ``except Exception`` (or bare ``except``) whose
   handler neither re-raises at its top level nor increments an obs counter
   — errors that vanish without a trace in metrics.jsonl. Narrow the
   exception type, re-raise, or call ``obs.swallowed_error(site)``.

R5 non-atomic-write: a direct ``open(..., "w"/"a"/"x")`` (or ``io.open``)
   in the configured atomic-write modules (``io/``, ``robust/``). A crash
   mid-write leaves a torn file the next run half-reads; persistence in
   those trees must go through ``robust.atomic.atomic_write*`` (temp +
   fsync + rename), or carry an explicit ``# photon: ignore[R5]`` stating
   why rename semantics are wrong (e.g. append-only logs).

R6 nan-handling: (a) ``x == nan`` / ``x != nan`` against ``jnp.nan`` /
   ``np.nan`` / ``math.nan`` anywhere — NaN compares unequal to everything
   including itself, so the test is constant (use ``jnp.isnan`` /
   ``np.isnan``); (b) in the hot-loop modules, ``jnp.where(jnp.isnan(...),
   ...)`` inside a function that increments no obs counter — silently
   patching NaNs in a hot loop hides numerical divergence from every
   downstream defense (solver rollback, coordinate rejection). Count the
   occurrence, or reject via the divergence machinery instead of papering
   over it.

R8 jax-free-import: a module-level ``import jax`` / ``from jax... import``
   in the configured jax-free modules (the post-hoc report path: ``obs/``,
   ``cli/report.py``, the avro/index readers). These modules are contractually
   importable in processes with no usable jax (report rebuilds on dev
   laptops, CI doc builds); a top-level import — even one wrapped in
   ``try``/``except`` — breaks or degrades that contract silently. Import
   jax inside the function that needs it, or under ``if TYPE_CHECKING:``
   for annotations.

R9 thread-context-race (whole-program; ``analysis/project.py``): an
   instance attribute or mutated module global written in one execution
   context (a thread entrypoint, discovered or configured) and read or
   written in another without a common lock held on both sides — held
   lexically via ``with self._lock:`` or provably inherited from every call
   site. Declare intent the call graph cannot see on the assignment line:
   ``# photon: guarded-by[lock_attr]`` (validated against the class's real
   lock attributes) or ``# photon: thread-confined`` for
   handoff-at-a-barrier patterns (written by one thread, read by another
   only after an Event/join rendezvous).

R10 refusal-ledger-drift (whole-program): the typed-refusal raise sites,
   the README refusal-ledger table, the support-matrix test pins, and the
   checked-in ``refusals.json`` inventory must agree. A documented fragment
   no raise site produces, a pin the ledger omits, a ledger row no pin
   covers, a refusal-phrased raise the ledger does not document, and a
   stale inventory are each findings.

R11 metric-contract (whole-program): every literal ``photon_*`` series
   registration is checked against the naming conventions (counters end
   ``_total`` and nothing else does; no Prometheus-reserved
   ``_count``/``_sum``/``_bucket`` suffixes; lowercase snake_case), one
   kind and one label-key set per family, and two-way drift against the
   README metrics reference.

R12 unused-suppression: a ``# photon: ignore[RULE]`` that suppresses no
   finding, or a ``guarded-by``/``thread-confined``/``lock-order``/
   ``static-arg`` annotation its rule never needed, is itself a finding
   (mypy's warn-unused-ignores) — stale suppressions silently disable
   future findings at that site. Only checked for rules that actually ran.

R13 lock-order-deadlock (whole-program; ``analysis/dataflow.py``): every
   ``with lock:`` acquisition while other locks are held adds a held->
   acquired edge to a global lock-acquisition graph, and a call made while
   holding a lock adds edges to every lock the callee may transitively
   acquire (propagated over the call graph). A cycle means two threads can
   take the same locks in opposite orders and deadlock. Pin the intended
   global order with ``# photon: lock-order[LockA < LockB]`` (lock names
   are ``Class.attr`` for instance locks, the bare name for module-level
   locks; validated against the known lock set) — the annotation vouches
   the contrary order is unreachable and deletes that edge.

R14 resource-lifecycle (whole-program): a Thread / WorkerPool / socket /
   file / mmap / HTTPServer object bound to a local name must be closed,
   joined, stopped or shut down on *every* control-flow path out of the
   function — including the paths an exception takes (per-function CFG
   with exception edges). ``with`` and ``try/finally`` release on all
   paths; ``daemon=True`` threads are exempt by design; returning the
   object, storing it on an attribute, or passing it to another call
   transfers ownership and ends local responsibility (the ``pool=`` idiom
   in ``io/data.py``).

R15 jit-tracer-hazard (whole-program): reachability from ``@jit`` is
   computed over the call graph, so helpers a decorated kernel calls are
   held to tracer discipline too, not just the decorated body (R2 covers
   that). Inside jit-reachable scopes: a Python ``if``/``while``/
   short-circuit on a traced value (helpers only), ``float()``/``int()``/
   ``bool()``/``.item()`` coercions of traced values, and host-side
   mutation of closed-over state (``global``/``nonlocal``/``self.attr``
   writes run once at trace time, not per call). Declare a legitimately
   static operand with ``# photon: static-arg[name]`` on the ``def`` line
   (validated against the real parameter list).

R16 fault-site-inventory (whole-program): the literal
   ``faults.check``/``faults.corrupt`` call sites and ``io_call(...,
   site=...)`` declarations, the checked-in ``faults.json`` inventory, the
   README fault-site table, and an at-least-one-test-exercises-it scan of
   ``tests/`` string literals must agree four ways (the R10 refusal-ledger
   pattern applied to the chaos surface). A stale or missing inventory is
   a finding; regenerate with ``--write-fault-inventory``.

Taint tracking is deliberately local and conservative: names become
"jax-typed" through parameter annotations (``Array``, ``jax.Array``, ...)
and through assignment from expressions rooted at ``jnp.`` / ``jax.`` calls
or other tainted names; host-valued attributes (``.shape``, ``.dtype``) and
host-valued jax calls (``jnp.shape``, ``jax.device_get``) stop propagation.
False negatives are accepted (the runtime transfer guard backstops them);
false positives should be rare enough to suppress by hand.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "R1": "implicit device transfer in a hot-loop module",
    "R2": "recompile hazard inside a @jit function",
    "R3": "dtype discipline (hardcoded itemsize / dtype literal)",
    "R4": "swallowed exception (no re-raise, no obs counter)",
    "R5": "non-atomic file write in an atomic-write module",
    "R6": "NaN mishandling (== nan compare / uncounted isnan patch)",
    "R7": "direct wall-clock timing in a timing-strict module (use obs.span)",
    "R8": "module-level jax import in a jax-free module",
    "R9": "cross-thread shared-state access with no common lock",
    "R10": "refusal ledger drift (code / README / test pins / refusals.json)",
    "R11": "photon_* metric-name contract violation",
    "R12": "unused suppression or annotation",
    "R13": "lock-order cycle across the call graph (deadlock hazard)",
    "R14": "resource not released on every path (incl. exception edges)",
    "R15": "tracer hazard in a @jit-reachable function",
    "R16": "fault-site inventory drift (code / faults.json / README / tests)",
}

# attributes whose value is host metadata, not an array: reading them off a
# jax array neither transfers nor yields an array
_HOST_ATTRS = {
    "shape",
    "dtype",
    "ndim",
    "size",
    "nbytes",
    "itemsize",
    "sharding",
    "device",
    "devices",
    "aval",
    "weak_type",
    "coordinate_id",
    "name",
}

# jax-rooted callables that return host values (not arrays)
_HOST_VALUED_CALLS = {
    "jax.numpy.shape",
    "jax.numpy.ndim",
    "jax.numpy.size",
    "jax.numpy.dtype",
    "jax.numpy.promote_types",
    "jax.numpy.result_type",
    "jax.numpy.issubdtype",
    "jax.device_get",
    "jax.device_count",
    "jax.local_device_count",
    "jax.process_count",
    "jax.process_index",
    "jax.default_backend",
    "jax.devices",
    "jax.local_devices",
    "jax.eval_shape",
    "jax.tree_util.tree_structure",
}

# methods on arrays that return host scalars/objects ('.item()' is flagged
# separately by R1; 'tolist' likewise transfers but appears in cold paths)
_HOST_VALUED_METHODS = {"item", "tolist", "block_until_ready"}

_ARRAY_ANNOTATIONS = {
    "Array",
    "ArrayLike",
    "jax.Array",
    "jnp.ndarray",
    "jax.numpy.ndarray",
    "chex.Array",
}

_ITEMSIZE_CONTEXT_RE = re.compile(
    r"bytes|itemsize|budget|hbm|frombuffer|memmap", re.IGNORECASE
)


@dataclasses.dataclass(frozen=True)
class RawFinding:
    line: int
    col: int
    rule: str
    message: str


AddFn = Callable[[int, int, str, str], None]


# --------------------------------------------------------------------------
# shared helpers


def _dotted(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _import_aliases(tree: ast.Module) -> Dict[str, str]:
    """local name -> canonical dotted module ('jnp' -> 'jax.numpy')."""
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _canon(dotted: Optional[str], aliases: Dict[str, str]) -> Optional[str]:
    if not dotted:
        return None
    head, _, rest = dotted.partition(".")
    head = aliases.get(head, head)
    return f"{head}.{rest}" if rest else head


def _is_jax_rooted(canonical: Optional[str]) -> bool:
    return bool(canonical) and (
        canonical == "jax" or canonical.startswith(("jax.", "jax_"))
    )


def _annotation_is_array(ann: Optional[ast.AST]) -> bool:
    if ann is None:
        return False
    for node in ast.walk(ann):
        d = _dotted(node)
        if d in _ARRAY_ANNOTATIONS:
            return True
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value in _ARRAY_ANNOTATIONS:
                return True
    return False


def _param_names(fn) -> List[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)] + [
        p.arg for p in (a.vararg, a.kwarg) if p is not None
    ]


def _expr_is_jaxy(node: ast.AST, tainted: Set[str], aliases: Dict[str, str]) -> bool:
    """Conservative 'this expression evaluates to a jax array'."""
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Attribute):
        if node.attr in _HOST_ATTRS:
            return False
        d = _canon(_dotted(node), aliases)
        if d and _is_jax_rooted(d):
            # bare jnp.float32 / jax.Array etc.: dtype/class objects
            return False
        return _expr_is_jaxy(node.value, tainted, aliases)
    if isinstance(node, ast.Call):
        d = _canon(_dotted(node.func), aliases)
        if d:
            if d in _HOST_VALUED_CALLS:
                return False
            if _is_jax_rooted(d):
                return True
        if isinstance(node.func, ast.Attribute):
            if node.func.attr in _HOST_VALUED_METHODS:
                return False
            # method call on a jaxy receiver: x.astype(...), x.sum(), ...
            return _expr_is_jaxy(node.func.value, tainted, aliases)
        return False
    if isinstance(node, ast.BinOp):
        return _expr_is_jaxy(node.left, tainted, aliases) or _expr_is_jaxy(
            node.right, tainted, aliases
        )
    if isinstance(node, ast.UnaryOp):
        return _expr_is_jaxy(node.operand, tainted, aliases)
    if isinstance(node, ast.Compare):
        return _expr_is_jaxy(node.left, tainted, aliases) or any(
            _expr_is_jaxy(c, tainted, aliases) for c in node.comparators
        )
    if isinstance(node, ast.Subscript):
        return _expr_is_jaxy(node.value, tainted, aliases)
    if isinstance(node, ast.IfExp):
        return _expr_is_jaxy(node.body, tainted, aliases) or _expr_is_jaxy(
            node.orelse, tainted, aliases
        )
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_expr_is_jaxy(e, tainted, aliases) for e in node.elts)
    return False


def _own_nodes(fn) -> List[ast.AST]:
    """All nodes of a function body EXCLUDING nested function/class bodies
    (those are analyzed in their own scope)."""
    out: List[ast.AST] = []
    stack: List[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        out.append(node)
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)
        ):
            continue
        stack.extend(ast.iter_child_nodes(node))
    return out


def _propagate_taint(
    fn, seed: Set[str], aliases: Dict[str, str], rounds: int = 3
) -> Set[str]:
    """Fixpoint (bounded) over single-name assignments in the function's own
    scope: a name assigned a jaxy expression becomes jaxy."""
    tainted = set(seed)
    nodes = _own_nodes(fn)
    for _ in range(rounds):
        before = len(tainted)
        for node in nodes:
            targets: List[ast.AST] = []
            value: Optional[ast.AST] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            elif isinstance(node, ast.AugAssign):
                targets, value = [node.target], node.value
            if value is None or not _expr_is_jaxy(value, tainted, aliases):
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)
        if len(tainted) == before:
            break
    return tainted


class _Module:
    """Parsed module + shared lookups for the rule passes."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        self.aliases = _import_aliases(tree)
        self.functions: Dict[str, ast.FunctionDef] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions.setdefault(node.name, node)

    def walk_functions(self):
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node


# --------------------------------------------------------------------------
# R1: implicit device transfer in hot-loop modules


def _run_r1(mod: _Module, add: AddFn) -> None:
    aliases = mod.aliases
    for fn in mod.walk_functions():
        seed = {
            p.arg
            for p in (*fn.args.posonlyargs, *fn.args.args, *fn.args.kwonlyargs)
            if _annotation_is_array(p.annotation)
        }
        tainted = _propagate_taint(fn, seed, aliases)
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            d = _canon(_dotted(node.func), aliases)
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "item"
                and not node.args
                and not node.keywords
            ):
                add(
                    node.lineno,
                    node.col_offset,
                    "R1",
                    ".item() forces a device->host sync; fetch explicitly "
                    "via utils.transfer.logged_fetch or keep on device",
                )
                continue
            if not node.args:
                continue
            first = node.args[0]
            if d in ("float", "int", "bool") and len(node.args) == 1:
                if _expr_is_jaxy(first, tainted, aliases):
                    add(
                        node.lineno,
                        node.col_offset,
                        "R1",
                        f"{d}() on a jax value blocks on an implicit "
                        "device->host transfer; use "
                        "utils.transfer.logged_fetch or keep on device",
                    )
            elif d in ("numpy.asarray", "numpy.array"):
                if _expr_is_jaxy(first, tainted, aliases):
                    add(
                        node.lineno,
                        node.col_offset,
                        "R1",
                        f"{d.replace('numpy', 'np')}() on a jax value is an "
                        "implicit device->host fetch; use jax.device_get via "
                        "utils.transfer.logged_fetch so the transfer is "
                        "explicit and counted",
                    )


# --------------------------------------------------------------------------
# R2: recompile hazards


def _static_names_from_jit(
    call: Optional[ast.Call], fn, add: AddFn
) -> Set[str]:
    """Static parameter names from a jit(...) call's static_argnums /
    static_argnames; reports malformed specs."""
    statics: Set[str] = set()
    if call is None:
        return statics
    params = _param_names(fn)
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names: List[str] = []
            ok = True
            if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, str):
                names = [kw.value.value]
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                for e in kw.value.elts:
                    if isinstance(e, ast.Constant) and isinstance(e.value, str):
                        names.append(e.value)
                    else:
                        ok = False
            else:
                ok = False
            if not ok:
                add(
                    kw.value.lineno,
                    kw.value.col_offset,
                    "R2",
                    "static_argnames must be a literal str/tuple of strs "
                    "(non-literal statics hide recompile keys)",
                )
            for n in names:
                if n not in params:
                    add(
                        kw.value.lineno,
                        kw.value.col_offset,
                        "R2",
                        f"static_argnames entry {n!r} matches no parameter "
                        f"of {fn.name}()",
                    )
                statics.add(n)
        elif kw.arg == "static_argnums":
            nums: List[int] = []
            ok = True
            if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, int):
                nums = [kw.value.value]
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                for e in kw.value.elts:
                    if isinstance(e, ast.Constant) and isinstance(e.value, int):
                        nums.append(e.value)
                    else:
                        ok = False
            else:
                ok = False
            if not ok:
                add(
                    kw.value.lineno,
                    kw.value.col_offset,
                    "R2",
                    "static_argnums must be a literal int/tuple of ints",
                )
            pos = [p.arg for p in (*fn.args.posonlyargs, *fn.args.args)]
            for i in nums:
                if 0 <= i < len(pos):
                    statics.add(pos[i])
                else:
                    add(
                        kw.value.lineno,
                        kw.value.col_offset,
                        "R2",
                        f"static_argnums entry {i} is out of range for "
                        f"{fn.name}()",
                    )
    # array-annotated statics: hashability aside, every distinct value is a
    # fresh compile cache key
    by_name = {
        p.arg: p
        for p in (*fn.args.posonlyargs, *fn.args.args, *fn.args.kwonlyargs)
    }
    for name in sorted(statics):
        p = by_name.get(name)
        if p is not None and _annotation_is_array(p.annotation):
            add(
                p.lineno,
                p.col_offset,
                "R2",
                f"parameter {name!r} is annotated as an array but marked "
                "static: arrays are unhashable (TypeError) and, as statics, "
                "would recompile per value",
            )
    return statics


def _jit_call_of_decorator(dec: ast.AST, aliases: Dict[str, str]):
    """(is_jit, jit_call_node_or_None) for one decorator expression."""
    d = _canon(_dotted(dec), aliases)
    if d in ("jax.jit", "jit"):
        return True, None  # bare @jax.jit
    if isinstance(dec, ast.Call):
        dc = _canon(_dotted(dec.func), aliases)
        if dc in ("jax.jit", "jit"):
            return True, dec  # @jax.jit(static_argnames=...)
        if dc in ("functools.partial", "partial") and dec.args:
            inner = _canon(_dotted(dec.args[0]), aliases)
            if inner in ("jax.jit", "jit"):
                return True, dec  # @partial(jax.jit, static_argnames=...)
    return False, None


def _names_in_branchable(test: ast.AST, aliases: Dict[str, str]) -> Set[str]:
    """Names referenced by a test expression, excluding host-valued contexts:
    ``x is None`` checks, ``.shape``-like attributes, len()/isinstance()/
    hasattr()/getattr() arguments, and host-valued jax calls."""
    names: Set[str] = set()
    skip_roots = (ast.Lambda,)

    def visit(node: ast.AST) -> None:
        if isinstance(node, skip_roots):
            return
        if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            return
        if isinstance(node, ast.Attribute):
            if node.attr in _HOST_ATTRS:
                return
            visit(node.value)
            return
        if isinstance(node, ast.Call):
            d = _canon(_dotted(node.func), aliases)
            if d in ("len", "isinstance", "hasattr", "getattr", "type") or (
                d in _HOST_VALUED_CALLS
            ):
                return
            if isinstance(node.func, ast.Attribute):
                if node.func.attr in _HOST_VALUED_METHODS:
                    return
                visit(node.func.value)
            for a in node.args:
                visit(a)
            for kw in node.keywords:
                visit(kw.value)
            return
        if isinstance(node, ast.Name):
            names.add(node.id)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(test)
    return names


def _check_jit_body(fn, statics: Set[str], aliases: Dict[str, str], add: AddFn):
    tracers = set(_param_names(fn)) - statics - {"self", "cls"}
    tainted = _propagate_taint(fn, tracers, aliases)
    for node in _own_nodes(fn):
        if isinstance(node, (ast.If, ast.While)):
            hit = _names_in_branchable(node.test, aliases) & tainted
            if hit:
                kind = "if" if isinstance(node, ast.If) else "while"
                add(
                    node.lineno,
                    node.col_offset,
                    "R2",
                    f"Python `{kind}` on tracer-typed value(s) "
                    f"{sorted(hit)} inside @jit {fn.name}(): traced branches "
                    "need jnp.where/lax.cond; a hashable value here means a "
                    "recompile per distinct value",
                )
        elif isinstance(node, ast.JoinedStr):
            hit: Set[str] = set()
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    hit |= _names_in_branchable(v.value, aliases) & tainted
            if hit:
                add(
                    node.lineno,
                    node.col_offset,
                    "R2",
                    f"f-string formats tracer value(s) {sorted(hit)} inside "
                    f"@jit {fn.name}(): formatting forces abstract-value "
                    "repr (or a sync once concrete); use jax.debug.print",
                )


def _run_r2(mod: _Module, add: AddFn) -> None:
    aliases = mod.aliases
    seen: Set[int] = set()
    # decorator form
    for fn in mod.walk_functions():
        for dec in fn.decorator_list:
            is_jit, call = _jit_call_of_decorator(dec, aliases)
            if is_jit:
                statics = _static_names_from_jit(call, fn, add)
                if id(fn) not in seen:
                    seen.add(id(fn))
                    _check_jit_body(fn, statics, aliases, add)
    # call form: jax.jit(func_name, ...)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _canon(_dotted(node.func), aliases)
        if d not in ("jax.jit", "jit") or not node.args:
            continue
        target = node.args[0]
        if isinstance(target, ast.Name) and target.id in mod.functions:
            fn = mod.functions[target.id]
            statics = _static_names_from_jit(node, fn, add)
            if id(fn) not in seen:
                seen.add(id(fn))
                _check_jit_body(fn, statics, aliases, add)


# --------------------------------------------------------------------------
# R3: dtype discipline


def _simple_statements(tree: ast.Module):
    """(enclosing_function_name, stmt) for statements that own their whole
    subtree (no nested statements), so identifier context is local."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fname = node.name
            for sub in _own_nodes(node):
                if isinstance(
                    sub, (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Return, ast.Expr)
                ):
                    yield fname, sub


def _run_r3(mod: _Module, dtype_strict: bool, add: AddFn) -> None:
    aliases = mod.aliases
    flagged: Set[Tuple[int, int]] = set()
    for fname, stmt in _simple_statements(mod.tree):
        idents = [fname]
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name):
                idents.append(node.id)
            elif isinstance(node, ast.Attribute):
                idents.append(node.attr)
        if not _ITEMSIZE_CONTEXT_RE.search(" ".join(idents)):
            continue
        for node in ast.walk(stmt):
            if not isinstance(node, ast.BinOp) or not isinstance(node.op, ast.Mult):
                continue
            for side in (node.left, node.right):
                if (
                    isinstance(side, ast.Constant)
                    and side.value in (4, 8)
                    and side.value is not True
                    and (side.lineno, side.col_offset) not in flagged
                ):
                    flagged.add((side.lineno, side.col_offset))
                    add(
                        side.lineno,
                        side.col_offset,
                        "R3",
                        f"hardcoded itemsize {side.value} in byte accounting; "
                        "derive it from the array's dtype.itemsize (an x64 "
                        "run makes this estimate wrong by 2x)",
                    )
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _canon(_dotted(node.func), aliases)
        if d == "numpy.float32":
            add(
                node.lineno,
                node.col_offset,
                "R3",
                "np.float32(...) cast: derive the dtype from the data "
                "(jnp.promote_types / x.dtype) instead of pinning f32",
            )
        elif isinstance(node.func, ast.Attribute) and node.func.attr == "astype":
            if node.args:
                arg = node.args[0]
                ad = _canon(_dotted(arg), aliases)
                if ad == "numpy.float32" or (
                    isinstance(arg, ast.Constant) and arg.value == "float32"
                ):
                    add(
                        node.lineno,
                        node.col_offset,
                        "R3",
                        ".astype(float32) literal: derive the dtype from the "
                        "data instead of pinning f32",
                    )
        elif dtype_strict and d in ("jax.numpy.array", "jax.numpy.asarray"):
            has_dtype = len(node.args) >= 2 or any(
                kw.arg == "dtype" for kw in node.keywords
            )
            if not has_dtype:
                short = "jnp." + d.rsplit(".", 1)[1]
                add(
                    node.lineno,
                    node.col_offset,
                    "R3",
                    f"{short}(...) without an explicit dtype in a "
                    "dtype-strict module: the result silently follows the "
                    "backend default; pass dtype= derived from the inputs",
                )


# --------------------------------------------------------------------------
# R4: swallow-and-continue


def _handler_is_accounted(handler: ast.ExceptHandler) -> bool:
    """True when the handler re-raises at its top level or increments an obs
    counter anywhere in its body. A call whose final segment ENDS WITH
    ``swallowed_error`` also counts, so modules below obs in the import graph
    can route through a lazy-import wrapper (e.g. ``_swallowed_error``)."""
    for stmt in handler.body:
        if isinstance(stmt, ast.Raise):
            return True
    for node in ast.walk(handler):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            seg = d.split(".")[-1] if d else ""
            if seg == "inc" or seg.endswith("swallowed_error"):
                return True
    return False


def _run_r4(mod: _Module, add: AddFn) -> None:
    aliases = mod.aliases
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        broad = node.type is None
        if node.type is not None:
            types = (
                node.type.elts if isinstance(node.type, ast.Tuple) else [node.type]
            )
            for t in types:
                d = _canon(_dotted(t), aliases) or ""
                if d.split(".")[-1] in ("Exception", "BaseException"):
                    broad = True
        if broad and not _handler_is_accounted(node):
            add(
                node.lineno,
                node.col_offset,
                "R4",
                "broad except swallows errors invisibly: narrow the type, "
                "re-raise at the handler's top level, or call "
                "obs.swallowed_error(site) so the swallow shows up in "
                "metrics.jsonl",
            )


# --------------------------------------------------------------------------
# R5: non-atomic file writes in atomic-write modules


def _open_write_mode(node: ast.Call, aliases: Dict[str, str]) -> Optional[str]:
    """The literal write mode of an ``open()`` / ``io.open()`` call, or None
    when the call isn't an open or the mode isn't a write mode. A non-literal
    mode is returned as ``"?"`` (flagged: it may be a write)."""
    d = _canon(_dotted(node.func), aliases)
    if d not in ("open", "io.open"):
        return None
    mode_node: Optional[ast.AST] = None
    if len(node.args) >= 2:
        mode_node = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode_node = kw.value
    if mode_node is None:
        return None  # default "r"
    if isinstance(mode_node, ast.Constant) and isinstance(mode_node.value, str):
        mode = mode_node.value
        return mode if any(c in mode for c in "wax+") else None
    return "?"


def _run_r5(mod: _Module, add: AddFn) -> None:
    aliases = mod.aliases
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        mode = _open_write_mode(node, aliases)
        if mode is None:
            continue
        what = (
            f"open(..., {mode!r})"
            if mode != "?"
            else "open() with a non-literal mode"
        )
        add(
            node.lineno,
            node.col_offset,
            "R5",
            f"{what} in an atomic-write module: a crash mid-write leaves a "
            "torn file; write through robust.atomic.atomic_write* "
            "(temp+fsync+rename) or justify with # photon: ignore[R5]",
        )


# --------------------------------------------------------------------------
# R6: NaN mishandling

_NAN_CONSTANTS = {"jax.numpy.nan", "numpy.nan", "math.nan", "numpy.NaN", "numpy.NAN"}


def _is_nan_expr(node: ast.AST, aliases: Dict[str, str]) -> bool:
    d = _canon(_dotted(node), aliases)
    if d in _NAN_CONSTANTS:
        return True
    # float("nan") / float("NaN")
    if (
        isinstance(node, ast.Call)
        and _canon(_dotted(node.func), aliases) == "float"
        and len(node.args) == 1
        and isinstance(node.args[0], ast.Constant)
        and isinstance(node.args[0].value, str)
        and node.args[0].value.lower() == "nan"
    ):
        return True
    return False


def _function_has_counter(fn) -> bool:
    """Same accounting convention as R4's handler check: a call whose final
    segment is ``inc`` or ends with ``swallowed_error`` marks the function as
    making its degraded path visible in metrics."""
    for node in _own_nodes(fn):
        if isinstance(node, ast.Call):
            # attr check, not _dotted: the idiomatic chain is
            # registry.counter(...).inc(...) whose base is a Call
            if isinstance(node.func, ast.Attribute) and (
                node.func.attr == "inc"
                or node.func.attr.endswith("swallowed_error")
            ):
                return True
            d = _dotted(node.func)
            if d and d.split(".")[-1].endswith("swallowed_error"):
                return True
    return False


def _run_r6(mod: _Module, hot: bool, add: AddFn) -> None:
    aliases = mod.aliases
    # (a) == / != against a NaN constant: always-constant comparison
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Compare):
            continue
        if not any(isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops):
            continue
        operands = [node.left, *node.comparators]
        if any(_is_nan_expr(o, aliases) for o in operands):
            add(
                node.lineno,
                node.col_offset,
                "R6",
                "comparison against nan is constant (NaN != NaN by IEEE 754): "
                "== nan is always False, != nan always True; use "
                "jnp.isnan/np.isnan",
            )
    if not hot:
        return
    # (b) jnp.where(jnp.isnan(...), ...) in a hot module with no counter in
    # the enclosing function: the NaN is silently replaced, invisible to the
    # divergence defenses
    for fn in mod.walk_functions():
        counted = None  # lazy: only compute when a candidate where() shows up
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            d = _canon(_dotted(node.func), aliases)
            if d not in ("jax.numpy.where", "numpy.where"):
                continue
            cond_has_isnan = any(
                isinstance(sub, ast.Call)
                and _canon(_dotted(sub.func), aliases)
                in ("jax.numpy.isnan", "numpy.isnan")
                for sub in ast.walk(node.args[0])
            )
            if not cond_has_isnan:
                continue
            if counted is None:
                counted = _function_has_counter(fn)
            if not counted:
                add(
                    node.lineno,
                    node.col_offset,
                    "R6",
                    f"where(isnan(...)) in hot function {fn.name}() silently "
                    "patches NaNs with no counter: increment an obs counter "
                    "alongside the patch, or reject the value through the "
                    "divergence machinery (isfinite + rollback) instead",
                )


# --------------------------------------------------------------------------


# --------------------------------------------------------------------------
# R7: direct wall-clock timing in timing-strict modules
#
# The timeline profiler (obs/timeline.py) can only attribute what flows
# through spans. A bare time.time()/time.perf_counter() pair in a hot-loop
# module measures something the timeline cannot see — the measurement is
# invisible to phase attribution, Chrome-trace export, and the JSONL stream.
# Route the section through obs.span(...) and read the
# span's duration_s instead. Cross-thread timestamp plumbing that cannot be
# a span (e.g. enqueue stamps handed to another thread) suppresses with a
# per-site ignore[R7] comment.

_TIMING_CALLS = {"time.time", "time.perf_counter", "time.monotonic"}


def _run_r7(mod: _Module, add: AddFn) -> None:
    aliases = mod.aliases
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        canonical = _canon(_dotted(node.func), aliases)
        if canonical in _TIMING_CALLS:
            add(
                node.lineno,
                node.col_offset,
                "R7",
                f"direct {canonical}() timing in a timing-strict module is "
                "invisible to the timeline profiler: wrap the section in "
                "obs.span(...) and read span.duration_s (suppress "
                "cross-thread timestamp plumbing with # photon: ignore[R7])",
            )


# --------------------------------------------------------------------------
# R8: module-level jax import in jax-free modules
#
# The report path (obs/, cli/report.py, the avro/index readers) must import
# in a process where jax is absent or poisoned — rebuilding report.html from
# artifacts must not require an accelerator stack. Only *module-level*
# imports break that; a function-level `import jax` inside the one code path
# that needs it is the sanctioned pattern (and what obs/run.py does), so the
# walk skips function bodies. `if TYPE_CHECKING:` blocks never execute at
# runtime and are skipped too. A try/except-guarded top-level import is
# still flagged: with jax installed it drags the whole stack into every
# importer anyway.


def _run_r8(mod: _Module, add: AddFn) -> None:
    def flag(node: ast.stmt, what: str) -> None:
        add(
            node.lineno,
            node.col_offset,
            "R8",
            f"module-level `{what}` in a jax-free module: the report path "
            "must import without a usable jax — move the import inside the "
            "function that needs it, or under `if TYPE_CHECKING:`",
        )

    def is_type_checking(test: ast.expr) -> bool:
        return (isinstance(test, ast.Name) and test.id == "TYPE_CHECKING") or (
            isinstance(test, ast.Attribute) and test.attr == "TYPE_CHECKING"
        )

    def visit(stmts: Sequence[ast.stmt]) -> None:
        for node in stmts:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # function-level imports are the sanctioned pattern
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax" or alias.name.startswith("jax."):
                        flag(node, f"import {alias.name}")
            elif isinstance(node, ast.ImportFrom):
                m = node.module or ""
                if node.level == 0 and (m == "jax" or m.startswith("jax.")):
                    flag(node, f"from {m} import ...")
            elif isinstance(node, ast.If):
                if not is_type_checking(node.test):
                    visit(node.body)
                visit(node.orelse)
            elif isinstance(node, ast.Try):
                visit(node.body)
                for h in node.handlers:
                    visit(h.body)
                visit(node.orelse)
                visit(node.finalbody)
            elif isinstance(node, (ast.With, ast.ClassDef)):
                visit(node.body)

    visit(mod.tree.body)


def run_rules(
    tree: ast.Module,
    *,
    hot: bool,
    dtype_strict: bool,
    atomic: bool = False,
    timing: bool = False,
    jax_free: bool = False,
    rules: Optional[Sequence[str]] = None,
) -> List[RawFinding]:
    """All rule passes over one parsed module. ``hot`` enables R1;
    ``dtype_strict`` enables R3's jnp.array-without-dtype subrule;
    ``atomic`` enables R5 (direct-write detection in persistence modules);
    ``timing`` enables R7 (wall-clock timing outside obs.span);
    ``jax_free`` enables R8 (no module-level jax import)."""
    mod = _Module(tree)
    out: List[RawFinding] = []
    enabled = set(rules) if rules is not None else set(RULES)

    def adder(rule: str) -> AddFn:
        def add(line: int, col: int, r: str, message: str) -> None:
            if r in enabled:
                out.append(RawFinding(line=line, col=col, rule=r, message=message))

        return add

    if hot and "R1" in enabled:
        _run_r1(mod, adder("R1"))
    if "R2" in enabled:
        _run_r2(mod, adder("R2"))
    if "R3" in enabled:
        _run_r3(mod, dtype_strict, adder("R3"))
    if "R4" in enabled:
        _run_r4(mod, adder("R4"))
    if atomic and "R5" in enabled:
        _run_r5(mod, adder("R5"))
    if "R6" in enabled:
        _run_r6(mod, hot, adder("R6"))
    if timing and "R7" in enabled:
        _run_r7(mod, adder("R7"))
    if jax_free and "R8" in enabled:
        _run_r8(mod, adder("R8"))
    out.sort(key=lambda f: (f.line, f.col, f.rule))
    return out


# --------------------------------------------------------------------------
# --explain: per-rule documentation, sourced from this module's docstring so
# the CLI text and the reference text are one artifact and cannot drift.


def _docstring_sections() -> Dict[str, str]:
    """The ``R<n> ...`` paragraphs of the module docstring, keyed by rule."""
    sections: Dict[str, str] = {}
    current: Optional[str] = None
    buf: List[str] = []
    for line in (__doc__ or "").splitlines():
        m = re.match(r"^(R\d+)\s", line)
        if m and m.group(1) in RULES:
            if current is not None:
                sections[current] = "\n".join(buf).rstrip()
            current, buf = m.group(1), [line]
        elif current is not None and (not line or line.startswith(" ")):
            buf.append(line)
        elif current is not None:
            sections[current] = "\n".join(buf).rstrip()
            current, buf = None, []
    if current is not None:
        sections[current] = "\n".join(buf).rstrip()
    return sections


# (bad, good) minimal examples per rule, printed by --explain
RULE_EXAMPLES: Dict[str, Tuple[str, str]] = {
    "R1": (
        "loss = float(loss_dev)          # blocks on device->host sync",
        'loss = logged_fetch(loss_dev, "cd.loss")  # counted, attributed',
    ),
    "R2": (
        "@jax.jit\ndef f(x):\n    if x > 0:            # tracer in Python control flow\n        return x",
        "@jax.jit\ndef f(x):\n    return jnp.where(x > 0, x, 0.0)",
    ),
    "R3": (
        "hbm_bytes = n_rows * n_cols * 4   # wrong for x64 inputs",
        "hbm_bytes = n_rows * n_cols * arr.dtype.itemsize",
    ),
    "R4": (
        "except Exception:\n    pass                    # error vanishes from metrics.jsonl",
        'except Exception:\n    obs.swallowed_error("decode")\n    part = None',
    ),
    "R5": (
        'with open(ckpt_path, "w") as f:   # torn file on crash\n    f.write(payload)',
        "atomic_write_text(ckpt_path, payload)  # temp + fsync + rename",
    ),
    "R6": (
        "if x == jnp.nan:                 # always False",
        "if bool(jnp.isnan(x)):",
    ),
    "R7": (
        "t0 = time.perf_counter()\nsolve()\ndt = time.perf_counter() - t0   # invisible to the timeline",
        'with obs.span("solver.solve"):\n    solve()',
    ),
    "R8": (
        "import jax                        # at module level in obs/",
        "def rebuild():\n    import jax    # only the caller that needs it pays",
    ),
    "R9": (
        "def _worker(self):\n    self._live = snap          # worker thread writes\n"
        "def poke(self):\n    return self._live          # main thread reads, no lock",
        "def _worker(self):\n    with self._lock:\n        self._live = snap\n"
        "def poke(self):\n    with self._lock:\n        return self._live\n"
        "# or, when a barrier transfers ownership:\n"
        "self._value = None  # photon: thread-confined — read only after _done.wait()",
    ),
    "R10": (
        'raise ValueError("streaming is not supported with mesh sharding")\n'
        "# ...but no README refusal-ledger row / test pin mentions it",
        "# README ledger row + tests/test_support_matrix.py pin + refusals.json\n"
        "# entry all match the raise site (regenerate with\n"
        "# --write-refusal-inventory)",
    ),
    "R11": (
        'REG.counter("photon_requests")    # counter without _total',
        'REG.counter("photon_requests_total")',
    ),
    "R12": (
        "x = compute()  # photon: ignore[R4] — but nothing fires here",
        "x = compute()  # stale suppression deleted",
    ),
    "R13": (
        "def flip(self):\n    with self._lock:\n        self._store.put(k)   # Store.put takes Store._lock\n"
        "# elsewhere: Store.drain() holds Store._lock, then calls back into\n"
        "# a method that takes self._lock — opposite order, deadlock",
        "# release before calling into the other object:\n"
        "def flip(self):\n    with self._lock:\n        k = self._key\n    self._store.put(k)\n"
        "# or pin the one true order (vouches the contrary edge is unreachable):\n"
        "# photon: lock-order[Scorer._lock < Store._lock]",
    ),
    "R14": (
        "def serve(self):\n    t = threading.Thread(target=self._run)\n    t.start()\n"
        "    self._warmup()        # raises -> t never joined, thread leaks",
        "def serve(self):\n    t = threading.Thread(target=self._run)\n    t.start()\n"
        "    try:\n        self._warmup()\n    finally:\n        self._stop.set()\n        t.join()",
    ),
    "R15": (
        "@jax.jit\ndef step(w, g):\n    return _clip(w - 0.1 * g)\n"
        "def _clip(x):\n    if x.sum() > 1e3:     # traced value in Python `if`,\n"
        "        return x / 10.0   # three calls below the jit boundary\n    return x",
        "def _clip(x):\n    return jnp.where(x.sum() > 1e3, x / 10.0, x)\n"
        "# or, if the operand really is static per compilation:\n"
        "def _clip(x, cap):  # photon: static-arg[cap]\n    ...",
    ),
    "R16": (
        'faults.check("solver.step")       # new chaos site...\n'
        "# ...absent from faults.json, the README fault-site table, and\n"
        "# every tests/ string literal",
        "# README fault-site row + a PHOTON_FAULTS test case mention\n"
        '# "solver.step"; faults.json regenerated with --write-fault-inventory',
    ),
}


def explain_rule(rule: str) -> str:
    """Human-readable doc block for one rule: summary, rationale, examples."""
    sections = _docstring_sections()
    out = [f"{rule}: {RULES[rule]}", ""]
    doc = sections.get(rule)
    if doc:
        out.extend([doc, ""])
    bad, good = RULE_EXAMPLES[rule]
    out.append("bad:")
    out.extend(f"    {line}" for line in bad.splitlines())
    out.append("good:")
    out.extend(f"    {line}" for line in good.splitlines())
    return "\n".join(out)
