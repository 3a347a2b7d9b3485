"""Resource-lifetime pass: device-memory and buffer-lifetime rules
(GL2xx). The static half of the HBM accounting substrate the unified
memory arbiter will be built on (ROADMAP top item); the runtime half is
gofr_tpu/testutil/hbmwatch.py.

GL201 — scope ``gofr_tpu/``. Use-after-donate: an argument passed at a
donated position of a ``jax.jit(..., donate_argnums=...)`` call site is
read, returned, or stored again AFTER the call. Donation invalidates
the buffer — JAX raises on access at best, and on some backends the
aliased memory is silently reused by the jit's outputs. The dataflow
runs over the enclosing function in statement order with
rebinding-kills: assigning the name (``self.cache = step(self.cache,
...)`` rebinds in the same statement and is clean) clears the taint;
loop bodies are analyzed twice so a donation in iteration N is seen by
a read in iteration N+1. Metadata reads (``.shape``/``.dtype``/
``.ndim``/``.nbytes``) survive donation (the aval outlives the buffer)
and are exempt, as is any line annotated ``# gl: consumed`` — the
escape hatch for flows the analyzer cannot see (e.g. a conditional
donation the caller re-checks).

GL202 — scope ``gofr_tpu/tpu/`` (the serving modules). Unaccounted
device allocations: a ``jnp.zeros/ones/full/empty[_like]``,
``jax.device_put``, or pool-row construction (``*init_cache`` /
``init_paged_cache`` / ``init_lora``) whose result is PERSISTED on the
instance (assigned to ``self.X`` directly, or via locals that later
flow into a ``self.X`` assignment) without flowing through the
accounting API (an ``hbm.account(...)`` or ``hbm.alloc(...)`` —
the arbiter's reclaim-then-retry lease form — wrapping the allocation
or its local). Transient allocations that die with the function are not
flagged — persistent buffers are exactly the arbiter's future lease
targets, and an allocation the registry cannot see is capacity the
arbiter cannot rebalance (one subsystem's RESOURCE_EXHAUSTED then
kills the process). Allocations inside jit-traced functions are
traced, not eager HBM, and are exempt.

GL203 — scope ``gofr_tpu/tpu/``. Unbounded request-path growth: an
append/insert into an instance- or module-level container from a
request/decode-path method (anything not construction/teardown) in a
class that contains NO eviction for that container — no pop/remove/
clear/del, no non-constructor reassignment. This is the leak shape
that killed the flat prefix cache: every request adds an entry, nothing
ever removes one, and steady-state HBM/host growth ends in
RESOURCE_EXHAUSTED.

GL204 — scope ``gofr_tpu/``. Fail-open OOM handling: an ``except`` arm
that names an OOM-class exception (``XlaRuntimeError``,
``ResourceExhausted*``, ``OutOfMemory*``, the arbiter's
``HBMExhausted``) — or string-matches
``RESOURCE_EXHAUSTED`` / ``out of memory`` inside a generic handler —
and neither re-raises nor routes to the admission-shed path
(``raise``, a ``*shed*``/``*admit*`` call, ``TooManyRequests``).
Swallowing OOM turns memory pressure into silent capacity loss; the
overload-safe answer is the AdmissionGate shed path (resilience.py).
"""

from __future__ import annotations

import ast
import re

from .base import Finding, SourceFile, _self_attr, in_framework, \
    project_parts
from .hotpath import _callee_last, _callee_root

# allocation constructors whose results are eager device buffers
_ALLOC_JNP = {"zeros", "ones", "full", "empty",
              "zeros_like", "ones_like", "full_like", "empty_like"}
_ALLOC_ANY = {"device_put"}
_ALLOC_SUBSTR = ("init_cache", "init_paged_cache", "init_lora")
# the declared accounting API (gofr_tpu/tpu/hbm.py): account() records
# post-hoc; alloc()/lease() are the arbiter's budgeted forms (lease +
# reclaim-then-retry + account), and alloc_sharded() is the PER-SHARD
# variant mesh engines use (per-device lease split + per-shard
# account) — all three match only as QUALIFIED hbm.alloc/hbm.lease/
# hbm.alloc_sharded (see _is_account_call): "alloc" is far too
# generic a method name to bless bare (the paged engine's block
# allocator is literally self._alloc.alloc)
_ACCOUNT_FNS = {"account"}
_ARBITER_FNS = {"alloc", "lease", "alloc_sharded", "tenant_lease"}


def _is_account_call(func) -> bool:
    last = _callee_last(func)
    if last in _ACCOUNT_FNS:
        return True
    if last in _ARBITER_FNS:
        return _callee_root(func) == "hbm"
    return False
# attribute reads that survive donation (metadata lives on the aval)
_META_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "sharding",
               "quantized"}
# construction/teardown methods: allocations and container writes here
# are setup, not request-path growth
_SETUP_NAMES = {"__init__", "__post_init__", "__del__", "close", "clear",
                "reset", "drain", "warmup", "stop", "shutdown"}
_GROW_CALLS = {"append", "add", "insert", "extend", "appendleft",
               "setdefault"}
_SHRINK_CALLS = {"pop", "popitem", "popleft", "remove", "discard",
                 "clear"}
_CONTAINER_CTORS = {"list", "dict", "set", "deque", "defaultdict",
                    "OrderedDict", "Counter"}
_OOM_TYPE_SUBSTR = ("XlaRuntimeError", "ResourceExhausted", "OutOfMemory",
                    "HBMExhausted")
_OOM_STR_RE = re.compile(r"RESOURCE_EXHAUSTED|out of memory",
                         re.IGNORECASE)
_SHED_SUBSTR = ("shed", "admit", "TooManyRequests")
_GL_CONSUMED_RE = re.compile(r"#\s*gl:\s*consumed\b")


def _donate_spec(call: ast.Call) -> tuple[set[int], set[str]]:
    """donate_argnums/donate_argnames of one jit(...) call."""
    nums: set[int] = set()
    names: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            elts = [kw.value]
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                elts = list(kw.value.elts)
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    nums.add(e.value)
        if kw.arg == "donate_argnames" and isinstance(
                kw.value, (ast.Tuple, ast.List, ast.Constant)):
            elts = kw.value.elts if not isinstance(kw.value, ast.Constant) \
                else [kw.value]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    names.add(e.value)
    return nums, names


def _is_jit_name(node: ast.expr) -> bool:
    return _callee_last(node) == "jit"


def _bind_name(node: ast.expr) -> str | None:
    """Callable identity at a call/assignment site: ``self._step_jit``
    and ``step_jit`` both key as their last name — donation info and
    call sites must agree whether the wrapper lives on self or a
    local/module binding."""
    return _self_attr(node) or (
        node.id if isinstance(node, ast.Name) else None)


def _collect_donors(tree: ast.AST) -> dict[str, tuple[set[int], set[str]]]:
    """name -> (donated positions, donated kwarg names) for every
    callable this module binds to a donating jit."""
    donors: dict[str, tuple[set[int], set[str]]] = {}

    def add(nm: str | None, nums: set[int], names: set[str]) -> None:
        if nm is None or not (nums or names):
            return
        have = donors.setdefault(nm, (set(), set()))
        have[0].update(nums)
        have[1].update(names)

    for node in ast.walk(tree):
        # X = jax.jit(fn, donate_argnums=...)  (optionally nested in
        # other calls on the RHS — rare, keep the direct form only)
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call) and \
                _is_jit_name(node.value.func) and node.value.args:
            nums, names = _donate_spec(node.value)
            for t in node.targets:
                add(_bind_name(t), nums, names)
        # @jax.jit(donate_argnums=...) / @partial(jax.jit, donate...)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                if _is_jit_name(dec.func) or (
                        _callee_last(dec.func) == "partial" and dec.args
                        and _is_jit_name(dec.args[0])):
                    nums, names = _donate_spec(dec)
                    add(node.name, nums, names)
    return donors


# -- GL201: use-after-donate dataflow ----------------------------------------

# taint variables: ("l", name) for locals, ("s", attr) for self.X
_Var = tuple[str, str]


def _var_of(node: ast.expr) -> _Var | None:
    a = _self_attr(node)
    if a is not None:
        return ("s", a)
    if isinstance(node, ast.Name):
        return ("l", node.id)
    return None


def _var_disp(v: _Var) -> str:
    return f"self.{v[1]}" if v[0] == "s" else v[1]


class _DonateFlow:
    """Statement-ordered taint propagation for one function body."""

    def __init__(self, sf: SourceFile, fn: ast.AST,
                 donors: dict[str, tuple[set[int], set[str]]],
                 out: list[Finding]):
        self.sf = sf
        self.fn = fn
        self.donors = donors
        self.out = out
        self._seen: set[tuple[int, _Var]] = set()

    # -- expression-level helpers -------------------------------------------
    def _donations(self, stmt: ast.stmt) -> list[tuple[_Var, ast.Call]]:
        """(var, call) for every Name/self-attr passed at a donated
        position of a donating callable anywhere in ``stmt``."""
        found: list[tuple[_Var, ast.Call]] = []
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            spec = self.donors.get(_bind_name(node.func) or "")
            if spec is None:
                continue
            nums, names = spec
            for i, arg in enumerate(node.args):
                if i in nums:
                    v = _var_of(arg)
                    if v is not None:
                        found.append((v, node))
            for kw in node.keywords:
                if kw.arg in names:
                    v = _var_of(kw.value)
                    if v is not None:
                        found.append((v, node))
        return found

    def _reads(self, node: ast.AST) -> list[tuple[_Var, int]]:
        """Every (var, line) read in ``node``, metadata reads pruned."""
        skip: set[int] = set()
        for n in ast.walk(node):
            if isinstance(n, ast.Attribute) and n.attr in _META_ATTRS:
                for sub in ast.walk(n):
                    skip.add(id(sub))
        out: list[tuple[_Var, int]] = []
        for n in ast.walk(node):
            if id(n) in skip:
                continue
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                out.append((("l", n.id), n.lineno))
            else:
                a = _self_attr(n)
                if a is not None and isinstance(n.ctx, ast.Load):
                    out.append((("s", a), n.lineno))
        return out

    def _check_reads(self, node: ast.AST, taint: dict[_Var, int]) -> None:
        for v, line in self._reads(node):
            dline = taint.get(v)
            if dline is None or (line, v) in self._seen:
                continue
            if _GL_CONSUMED_RE.search(self.sf.comments.get(line, "")):
                continue
            self._seen.add((line, v))
            self.out.append(Finding(
                self.sf.rel, line, "GL201",
                f"{_var_disp(v)} used after being donated at line "
                f"{dline} in {self.fn.name} — the donated buffer is "
                f"invalidated; rebind the jit's output (or annotate "
                f"`# gl: consumed`)"))

    def _kills(self, target: ast.expr, taint: dict[_Var, int]) -> None:
        stack = [target]
        while stack:
            t = stack.pop()
            if isinstance(t, (ast.Tuple, ast.List)):
                stack.extend(t.elts)
                continue
            if isinstance(t, ast.Starred):
                stack.append(t.value)
                continue
            v = _var_of(t)
            if v is not None:
                taint.pop(v, None)

    # -- statement walk ------------------------------------------------------
    def exec_stmts(self, stmts: list[ast.stmt],
                   taint: dict[_Var, int]) -> dict[_Var, int]:
        for s in stmts:
            taint = self.exec_stmt(s, taint)
        return taint

    def exec_stmt(self, s: ast.stmt,
                  taint: dict[_Var, int]) -> dict[_Var, int]:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            return taint  # nested scopes: analyzed on their own
        if isinstance(s, ast.If):
            self._check_reads(s.test, taint)
            t1 = self.exec_stmts(s.body, dict(taint))
            t2 = self.exec_stmts(s.orelse, dict(taint))
            return {**t1, **t2}
        if isinstance(s, (ast.For, ast.AsyncFor)):
            self._check_reads(s.iter, taint)
            self._kills(s.target, taint)
            t1 = self.exec_stmts(s.body, dict(taint))
            # second pass: loop-carried taint (donated in iteration N,
            # read in N+1); _seen dedupes the re-walk
            t2 = self.exec_stmts(s.body, {**taint, **t1})
            merged = {**taint, **t2}
            return self.exec_stmts(s.orelse, merged)
        if isinstance(s, ast.While):
            self._check_reads(s.test, taint)
            t1 = self.exec_stmts(s.body, dict(taint))
            self._check_reads(s.test, t1)
            t2 = self.exec_stmts(s.body, {**taint, **t1})
            merged = {**taint, **t2}
            return self.exec_stmts(s.orelse, merged)
        if isinstance(s, ast.Try):
            t_body = self.exec_stmts(s.body, dict(taint))
            merged = {**taint, **t_body}
            for h in s.handlers:
                merged = {**merged, **self.exec_stmts(h.body, dict(merged))}
            merged = self.exec_stmts(s.orelse, merged)
            return self.exec_stmts(s.finalbody, merged)
        if isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self._check_reads(item.context_expr, taint)
                if item.optional_vars is not None:
                    self._kills(item.optional_vars, taint)
            return self.exec_stmts(s.body, taint)

        # simple statement: reads checked against PRE-state, then the
        # statement's own donations taint, then assignment targets kill
        # (targets bind the jit's OUTPUT — `x = step(x)` is clean)
        self._check_reads(s, taint)
        new_taint = [(v, call.lineno) for v, call in self._donations(s)]
        for v, line in new_taint:
            taint[v] = line
        if isinstance(s, ast.Assign):
            for t in s.targets:
                self._kills(t, taint)
        elif isinstance(s, (ast.AugAssign, ast.AnnAssign)):
            self._kills(s.target, taint)
        elif isinstance(s, ast.Delete):
            for t in s.targets:
                self._kills(t, taint)
        return taint


# -- GL202 helpers -----------------------------------------------------------

def _is_alloc(call: ast.Call) -> bool:
    last = _callee_last(call.func)
    if last is None:
        return False
    if last in _ALLOC_ANY:
        return True
    if any(sub in last for sub in _ALLOC_SUBSTR):
        return True
    return last in _ALLOC_JNP and _callee_root(call.func) == "jnp"


def _flat_stmts(body: list[ast.stmt]) -> list[ast.stmt]:
    """Statements of a function in source order, compound bodies
    flattened (GL202's local-flow scan only needs lexical order)."""
    out: list[ast.stmt] = []
    for s in body:
        out.append(s)
        for attr in ("body", "orelse", "finalbody"):
            out.extend(_flat_stmts(getattr(s, attr, []) or []))
        for h in getattr(s, "handlers", []) or []:
            out.extend(_flat_stmts(h.body))
    return [s for s in out
            if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef))]


# calls the allocated buffer flows THROUGH unchanged: the result still
# holds (or aliases) the allocation, so persistence propagates across
# them — unlike a dispatch call, which consumes its operands
_PASSTHROUGH = {"block_until_ready", "device_put"} | _ACCOUNT_FNS


def _persist_roots(value: ast.expr) -> set[int]:
    """ids of nodes in 'persisted position' of a value expression: the
    root, descending through pass-through wrappers and container
    displays. An allocation that only appears as an operand of some
    OTHER call (e.g. a padded-tokens buffer fed to a dispatch) is
    consumed by that call, not persisted by the assignment."""
    out: set[int] = set()
    stack = [value]
    while stack:
        n = stack.pop()
        out.add(id(n))
        if isinstance(n, ast.Call) and \
                _callee_last(n.func) in _PASSTHROUGH:
            stack.extend(n.args)
            stack.extend(kw.value for kw in n.keywords)
        elif isinstance(n, (ast.Tuple, ast.List, ast.Set)):
            stack.extend(n.elts)
        elif isinstance(n, ast.Dict):
            stack.extend(v for v in n.values if v is not None)
        elif isinstance(n, ast.Starred):
            stack.append(n.value)
        elif isinstance(n, ast.NamedExpr):
            stack.append(n.value)
        elif isinstance(n, ast.IfExp):
            stack.extend((n.body, n.orelse))
    return out


def _account_wraps(stmt: ast.stmt, node: ast.Call) -> bool:
    """Is ``node`` (an allocation) nested inside an account(...) call
    within its own statement?"""
    for n in ast.walk(stmt):
        if isinstance(n, ast.Call) and _is_account_call(n.func):
            if any(sub is node for sub in ast.walk(n)):
                return True
    return False


# -- the pass ----------------------------------------------------------------

class ResourcePass:
    def __init__(self):
        self.findings: list[Finding] = []

    def feed(self, sf: SourceFile) -> None:
        if sf.tree is None or not in_framework(sf.path):
            return
        donors = _collect_donors(sf.tree)
        jit_ids = self._jit_fn_ids(sf.tree, donors)
        # serving-module scope = gofr_tpu/tpu/ — the transport
        # (wire.py & co.) lives outside tpu/ and is excluded by the
        # path test alone
        in_tpu = "tpu" in project_parts(sf.path)
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if id(node) in jit_ids:
                    continue  # traced code: donation/allocation rules
                    # apply to the HOST side only
                if donors:
                    flow = _DonateFlow(sf, node, donors, self.findings)
                    flow.exec_stmts(list(node.body), {})
                if in_tpu:
                    self._gl202_fn(sf, node)
        if in_tpu:
            self._gl203(sf, jit_ids)
        self._gl204(sf)

    def _jit_fn_ids(self, tree: ast.AST,
                    donors: dict[str, tuple[set[int], set[str]]]
                    ) -> set[int]:
        """ids of function defs that are jit-traced (decorated, or
        wrapped by a jax.jit(fn) assignment anywhere in the module)."""
        ids: set[int] = set()
        defs = {n.name: n for n in ast.walk(tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_jit_name(dec):
                        ids.add(id(node))
                    elif isinstance(dec, ast.Call) and (
                            _is_jit_name(dec.func)
                            or (_callee_last(dec.func) == "partial"
                                and dec.args
                                and _is_jit_name(dec.args[0]))):
                        ids.add(id(node))
            if isinstance(node, ast.Call) and _is_jit_name(node.func) \
                    and node.args:
                fn = defs.get(_callee_last(node.args[0]) or "")
                if fn is not None:
                    ids.add(id(fn))
        return ids

    # -- GL202 ---------------------------------------------------------------
    def _gl202_fn(self, sf: SourceFile, fn: ast.AST) -> None:
        stmts = _flat_stmts(list(fn.body))
        for si, stmt in enumerate(stmts):
            if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                continue
            targets = stmt.targets if isinstance(stmt, ast.Assign) \
                else [stmt.target]
            value = stmt.value
            if value is None:
                continue
            proots = _persist_roots(value)
            allocs = [n for n in ast.walk(value)
                      if isinstance(n, ast.Call) and _is_alloc(n)
                      and id(n) in proots]
            if not allocs:
                continue
            self_attr: str | None = None
            locals_: set[str] = set()
            for t in targets:
                for tt in ast.walk(t):
                    a = _self_attr(tt)
                    if a is not None:
                        self_attr = a
                    elif isinstance(tt, ast.Name) and \
                            isinstance(tt.ctx, ast.Store):
                        locals_.add(tt.id)
            for alloc in allocs[:1]:  # one finding per statement
                if _account_wraps(stmt, alloc):
                    continue
                if self_attr is not None:
                    self._flag_202(sf, alloc, fn,
                                   f"self.{self_attr}")
                    continue
                if not locals_:
                    continue  # transient: consumed by this statement
                persisted = self._local_persists(stmts[si + 1:], locals_)
                if persisted is not None:
                    self._flag_202(sf, alloc, fn, persisted)

    def _local_persists(self, later: list[ast.stmt],
                        derived: set[str]) -> str | None:
        """Follow a local allocation through later statements: flowing
        into an account(...) call clears it; flowing into a self.X
        assignment persists it. Returns the persisting `self.X` (or
        None when the allocation stays function-local / accounted)."""
        derived = set(derived)
        for stmt in later:
            if not isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.Expr)):
                continue
            value = getattr(stmt, "value", None)
            if value is None:
                continue
            for n in ast.walk(value):
                if isinstance(n, ast.Call) and \
                        _is_account_call(n.func) and any(
                            isinstance(sub, ast.Name)
                            and sub.id in derived
                            for sub in ast.walk(n)):
                    return None  # flowed through the accounting API
            # the name persists/propagates only when it sits in a
            # persisted position of the value (pass-through wrappers /
            # container displays) — feeding it to a dispatch consumes it
            proots = _persist_roots(value)
            touches = any(isinstance(n, ast.Name) and n.id in derived
                          and isinstance(n.ctx, ast.Load)
                          and id(n) in proots
                          for n in ast.walk(value))
            if not touches:
                continue
            targets = stmt.targets if isinstance(stmt, ast.Assign) else \
                [stmt.target] if isinstance(stmt, ast.AnnAssign) else []
            for t in targets:
                for tt in ast.walk(t):
                    a = _self_attr(tt)
                    if a is not None:
                        return f"self.{a}"
                    if isinstance(tt, ast.Name) and \
                            isinstance(tt.ctx, ast.Store):
                        derived.add(tt.id)
        return None

    def _flag_202(self, sf: SourceFile, alloc: ast.Call, fn: ast.AST,
                  target: str) -> None:
        name = _callee_last(alloc.func)
        self.findings.append(Finding(
            sf.rel, alloc.lineno, "GL202",
            f"device allocation {name}() persisted to {target} in "
            f"{fn.name} without flowing through hbm.account() — "
            f"unaccounted HBM is invisible to the memory arbiter"))

    # -- GL203 ---------------------------------------------------------------
    def _gl203(self, sf: SourceFile, jit_ids: set[int]) -> None:
        # jit-traced functions are excluded: a container write there is
        # a TRACED write — GL103's territory, and reporting it twice
        # would double-bill one defect
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef):
                self._gl203_class(sf, node, jit_ids)
        self._gl203_module(sf, jit_ids)

    def _container_attrs(self, cls: ast.ClassDef) -> set[str]:
        out: set[str] = set()
        for node in ast.walk(cls):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = getattr(node, "value", None)
            is_container = isinstance(value, (ast.List, ast.Dict, ast.Set,
                                              ast.ListComp, ast.DictComp,
                                              ast.SetComp)) or (
                isinstance(value, ast.Call)
                and _callee_last(value.func) in _CONTAINER_CTORS)
            if not is_container:
                continue
            if (isinstance(value, ast.Call)
                    and _callee_last(value.func) == "deque"
                    and any(kw.arg == "maxlen"
                            and not (isinstance(kw.value, ast.Constant)
                                     and kw.value.value is None)
                            for kw in value.keywords)):
                # deque(maxlen=N) is a bounded ring: append() evicts
                # from the head once full — growth there is not a leak
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                a = _self_attr(t)
                if a is not None:
                    out.add(a)
        return out

    def _is_const_reset(self, value: ast.expr) -> bool:
        """`self.X[i] = []` / `= None` / `= 0` resets a cell — eviction
        shape, not growth."""
        if isinstance(value, ast.Constant):
            return True
        return isinstance(value, (ast.List, ast.Dict, ast.Set)) and \
            not getattr(value, "elts", None) and \
            not getattr(value, "keys", None)

    def _gl203_class(self, sf: SourceFile, cls: ast.ClassDef,
                     jit_ids: set[int]) -> None:
        attrs = self._container_attrs(cls)
        if not attrs:
            return
        shrunk: set[str] = set()
        grow: list[tuple[str, int, str]] = []  # (attr, line, method)
        for m in cls.body:
            if not isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    or id(m) in jit_ids:
                continue
            setup = m.name in _SETUP_NAMES or \
                m.name.lstrip("_").startswith(("evict", "invalidate",
                                               "retire", "reap", "prune",
                                               "expire", "trim", "load_",
                                               "register"))
            for node in ast.walk(m):
                # X.pop()/remove()/clear() — eviction anywhere counts
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute):
                    base = _self_attr(node.func.value)
                    if isinstance(node.func.value, ast.Subscript):
                        base = _self_attr(node.func.value.value)
                    if base in attrs:
                        if node.func.attr in _SHRINK_CALLS:
                            shrunk.add(base)
                        elif node.func.attr in _GROW_CALLS and not setup:
                            grow.append((base, node.lineno, m.name))
                if isinstance(node, ast.Delete):
                    for t in node.targets:
                        if isinstance(t, ast.Subscript):
                            base = _self_attr(t.value)
                            if base in attrs:
                                shrunk.add(base)
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        a = _self_attr(t)
                        if a in attrs and m.name != "__init__":
                            shrunk.add(a)  # wholesale reassignment
                        if isinstance(t, ast.Subscript):
                            base = _self_attr(t.value)
                            if base in attrs:
                                if self._is_const_reset(node.value):
                                    shrunk.add(base)
                                elif not setup:
                                    grow.append((base, t.lineno, m.name))
        for attr, line, meth in grow:
            if attr in shrunk:
                continue
            self.findings.append(Finding(
                sf.rel, line, "GL203",
                f"self.{attr} grows in request-path method {meth} and "
                f"the class never evicts from it — unbounded steady-"
                f"state growth (the flat-prefix-cache leak shape)"))

    def _gl203_module(self, sf: SourceFile, jit_ids: set[int]) -> None:
        containers = {
            t.id
            for node in sf.tree.body if isinstance(node, ast.Assign)
            for t in node.targets if isinstance(t, ast.Name)
            and (isinstance(node.value, (ast.List, ast.Dict, ast.Set,
                                         ast.ListComp, ast.DictComp))
                 or (isinstance(node.value, ast.Call)
                     and _callee_last(node.value.func)
                     in _CONTAINER_CTORS))
        }
        if not containers:
            return
        shrunk: set[str] = set()
        grow: list[tuple[str, int, str]] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                    or id(node) in jit_ids:
                continue
            setup = node.name in _SETUP_NAMES
            for n in ast.walk(node):
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        isinstance(n.func.value, ast.Name) and \
                        n.func.value.id in containers:
                    if n.func.attr in _SHRINK_CALLS:
                        shrunk.add(n.func.value.id)
                    elif n.func.attr in _GROW_CALLS and not setup:
                        grow.append((n.func.value.id, n.lineno, node.name))
                if isinstance(n, ast.Delete):
                    for t in n.targets:
                        if isinstance(t, ast.Subscript) and \
                                isinstance(t.value, ast.Name) and \
                                t.value.id in containers:
                            shrunk.add(t.value.id)
                if isinstance(n, ast.Assign):
                    for t in n.targets:
                        if isinstance(t, ast.Subscript) and \
                                isinstance(t.value, ast.Name) and \
                                t.value.id in containers and not setup \
                                and not self._is_const_reset(n.value):
                            grow.append((t.value.id, t.lineno, node.name))
        for name, line, meth in grow:
            if name in shrunk:
                continue
            self.findings.append(Finding(
                sf.rel, line, "GL203",
                f"module container {name!r} grows in {meth} and nothing "
                f"in this module ever evicts from it — unbounded "
                f"steady-state growth"))

    # -- GL204 ---------------------------------------------------------------
    def _names_oom_type(self, type_node: ast.expr | None) -> bool:
        if type_node is None:
            return False
        for n in ast.walk(type_node):
            last = _callee_last(n) if isinstance(
                n, (ast.Attribute, ast.Name)) else None
            if last and any(sub in last for sub in _OOM_TYPE_SUBSTR):
                return True
        return False

    def _handles_oom(self, body: list[ast.stmt]) -> bool:
        """Does this block rethrow or route to the shed path?"""
        for s in body:
            for n in ast.walk(s):
                if isinstance(n, ast.Raise):
                    return True
                if isinstance(n, ast.Call):
                    last = _callee_last(n.func) or ""
                    if any(sub.lower() in last.lower()
                           for sub in _SHED_SUBSTR):
                        return True
                if isinstance(n, ast.Name) and any(
                        sub in n.id for sub in ("TooManyRequests",)):
                    return True
        return False

    def _gl204(self, sf: SourceFile) -> None:
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if self._names_oom_type(node.type):
                if not self._handles_oom(node.body):
                    self.findings.append(Finding(
                        sf.rel, node.lineno, "GL204",
                        "OOM-class exception swallowed without rethrow "
                        "or admission-shed routing — fail-open OOM "
                        "handling turns memory pressure into silent "
                        "capacity loss"))
                continue
            # generic handler string-matching RESOURCE_EXHAUSTED: the
            # matching If arm must rethrow or shed
            for n in ast.walk(node):
                if not isinstance(n, ast.If):
                    continue
                has_oom_str = any(
                    isinstance(c, ast.Constant)
                    and isinstance(c.value, str)
                    and _OOM_STR_RE.search(c.value)
                    for c in ast.walk(n.test))
                if has_oom_str and not self._handles_oom(n.body):
                    self.findings.append(Finding(
                        sf.rel, n.lineno, "GL204",
                        "RESOURCE_EXHAUSTED matched and swallowed "
                        "without rethrow or admission-shed routing — "
                        "fail-open OOM handling turns memory pressure "
                        "into silent capacity loss"))
