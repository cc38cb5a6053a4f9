"""Host-sync rule (FC301): blocking host↔device transfers on the
serving hot path.

Hazard: on TPU the scheduler's throughput lives or dies by keeping the
device queue full. A single stray ``np.asarray(device_value)`` /
``jax.device_get`` / implicit ``bool(device_value)`` inside the
dispatch path blocks the host on the device, turning the async
pipeline back into lock-step. The engine's design makes
collection (``ServingEngine._collect_oldest`` /
``_collect_prefill_run``) the ONLY blocking points — those carry
explicit inline suppressions with a justification; anything else that
trips this rule is a scheduling bug. Real example: before PR 2, prefill
results were fetched inside admission, which silently absorbed in-flight
decode time into the prefill wall clock — exactly the call shape this
rule reports.

Mechanics: for every serving-scheduler-shaped class (a ``step`` method
plus ``_dispatch*``/``_collect*`` methods), build the self-method call
graph reachable from the hot entry points, then taint device values at
two levels — ARR (2): results of ``jnp.*``/``jax.*``/jitted ``*_j`` /
``*_impl`` calls and subscripts into device containers; CONT (1):
containers (deques/dicts/lists) those values were stored into. Host
materialization sinks fire on ARR (and on CONT for the whole-container
transfers ``np.asarray``/``jax.device_get``); ``int()``/``float()`` /
``np.asarray``/``jax.device_get`` results are HOST (laundering), so the
designed sync point doesn't taint everything downstream of it. Each
finding reports the call chain from the entry point.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from .core import Finding, FileContext
from .scopes import FuncNode, dotted, tail_of

_ENTRY_NAMES = ("step",)
_ENTRY_PREFIXES = ("_dispatch", "_collect", "_admit")

# call heads producing device values (level 2)
_DEVICE_HEAD_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "jax.random.",
                         "jax.nn.")
_DEVICE_EXACT = {"jax.device_put"}
# attribute-call suffixes that are jitted/compiled callables by this
# repo's convention (serving engine jits everything into *_j; decoder
# impls are *_impl)
_DEVICE_CALL_SUFFIXES = ("_j", "_impl")

# laundering: these RETURN host values (and are sinks when fed device)
_LAUNDER_HEADS = {"np.asarray", "np.array", "numpy.asarray",
                  "numpy.array", "jax.device_get", "int", "float",
                  "bool"}
_LAUNDER_METHODS = {"item", "tolist", "numpy"}
# container ops whose result keeps the container's element level
_CONTAINER_GETTERS = {"popleft", "pop", "get", "peek", "copy"}

_SINK_WHOLE = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "jax.device_get", "jax.block_until_ready"}
_SINK_CASTS = {"bool", "int", "float"}
_SINK_METHODS = {"block_until_ready", "item", "tolist"}


class _Taint:
    """Expression device-level evaluator for one method body."""

    def __init__(self, local: Dict[str, int], attrs: Dict[str, int]):
        self.local = local      # local name -> level
        self.attrs = attrs      # self-attr name -> level

    def level(self, expr) -> int:
        if expr is None:
            return 0
        if isinstance(expr, ast.Name):
            return self.local.get(expr.id, 0)
        if isinstance(expr, ast.Attribute):
            if isinstance(expr.value, ast.Name) and \
                    expr.value.id == "self":
                return self.attrs.get(expr.attr, 0)
            return 0
        if isinstance(expr, ast.Subscript):
            base = self.level(expr.value)
            return 2 if base else 0   # element of a device container
        if isinstance(expr, ast.Call):
            return self._call_level(expr)
        if isinstance(expr, (ast.List, ast.Tuple, ast.Set)):
            lv = max((self.level(e) for e in expr.elts), default=0)
            return 1 if lv else 0
        if isinstance(expr, ast.Dict):
            lv = max((self.level(v) for v in expr.values if v), default=0)
            return 1 if lv else 0
        if isinstance(expr, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            lv = self.level(expr.elt)
            # comprehension over a device container yields elements
            for gen in expr.generators:
                if self.level(gen.iter):
                    lv = max(lv, 2)
            return 1 if lv else 0
        if isinstance(expr, ast.IfExp):
            return max(self.level(expr.body), self.level(expr.orelse))
        if isinstance(expr, ast.BinOp):
            return max(self.level(expr.left), self.level(expr.right))
        if isinstance(expr, (ast.UnaryOp,)):
            return self.level(expr.operand)
        if isinstance(expr, ast.Starred):
            return self.level(expr.value)
        return 0

    def _call_level(self, call: ast.Call) -> int:
        head = dotted(call.func)
        if head in _LAUNDER_HEADS:
            return 0
        if isinstance(call.func, ast.Attribute):
            if call.func.attr in _LAUNDER_METHODS:
                return 0
            if call.func.attr in _CONTAINER_GETTERS:
                return self.level(call.func.value)
            if call.func.attr.endswith(_DEVICE_CALL_SUFFIXES):
                return 2
        if head:
            if head in _DEVICE_EXACT:
                return 2
            if head.startswith(_DEVICE_HEAD_PREFIXES):
                return 2
        # unknown call: containers/arrays flow through (iter/next/list)
        lv = max((self.level(a) for a in call.args), default=0)
        return lv


class _MethodInfo:
    def __init__(self, node):
        self.node = node
        self.calls: Set[str] = set()

    def collect_calls(self):
        for sub in ast.walk(self.node):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    isinstance(sub.func.value, ast.Name) and \
                    sub.func.value.id == "self":
                self.calls.add(sub.func.attr)


def _local_taint(fn_node, attrs: Dict[str, int]) -> Dict[str, int]:
    """Fixed-point device level of local names: only BARE-name targets
    are tainted (`cache.k, v = devcall()` taints nothing local — the
    attribute store is the cache object's business, not this scope's)."""
    local: Dict[str, int] = {}
    changed = True
    while changed:
        changed = False
        tt = _Taint(local, attrs)
        for sub in ast.walk(fn_node):
            pairs = []
            if isinstance(sub, ast.Assign):
                lv = tt.level(sub.value)
                if lv:
                    for t in sub.targets:
                        pairs.extend((n, lv) for n in _bare_names(t))
            elif isinstance(sub, ast.For):
                lv = tt.level(sub.iter)
                if lv:
                    # iterating a device container binds elements
                    pairs.extend((n, 2 if lv == 1 else lv)
                                 for n in _bare_names(sub.target))
            for name, lv in pairs:
                if local.get(name, 0) < lv:
                    local[name] = lv
                    changed = True
    return local


def _bare_names(target) -> List[str]:
    out = []
    if isinstance(target, ast.Name):
        out.append(target.id)
    elif isinstance(target, (ast.Tuple, ast.List)):
        for e in target.elts:
            out.extend(_bare_names(e))
    return out


def _attr_fixpoint(methods: Dict[str, _MethodInfo]) -> Dict[str, int]:
    attrs: Dict[str, int] = {}
    changed = True
    while changed:
        changed = False
        for mi in methods.values():
            local = _local_taint(mi.node, attrs)
            tt = _Taint(local, attrs)
            for sub in ast.walk(mi.node):
                updates = []
                if isinstance(sub, ast.Assign):
                    lv = tt.level(sub.value)
                    if lv:
                        for t in sub.targets:
                            for name, via_sub in _self_attr_targets(t):
                                # storing INTO self.X[...] makes X a
                                # container of device values
                                updates.append((name, 1 if via_sub
                                                else lv))
                elif isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr in ("append", "appendleft", "add",
                                          "extend", "insert"):
                    names = [n for n, _ in
                             _self_attr_targets(sub.func.value)]
                    if names and any(tt.level(a) for a in sub.args):
                        updates.extend((n, 1) for n in names)
                for name, lv in updates:
                    if attrs.get(name, 0) < lv:
                        attrs[name] = lv
                        changed = True
    return attrs


def _self_attr_targets(node) -> List:
    """[(attr_name, via_subscript)] for self.X / self.X[...] targets."""
    out = []
    if isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            out.extend(_self_attr_targets(e))
        return out
    via_sub = False
    while isinstance(node, ast.Subscript):
        node = node.value
        via_sub = True
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        out.append((node.attr, via_sub))
    return out


def _reachable(methods: Dict[str, _MethodInfo]) -> Dict[str, List[str]]:
    """method -> shortest call chain from a hot entry point. `step` is
    the preferred root (chains read "step -> _dispatch_chunk"); any
    dispatch/collect method it doesn't reach seeds its own chain."""
    chains: Dict[str, List[str]] = {}

    def bfs(roots):
        frontier = list(roots)
        while frontier:
            nxt = []
            for name in frontier:
                for callee in sorted(methods[name].calls):
                    if callee in methods and callee not in chains:
                        chains[callee] = chains[name] + [callee]
                        nxt.append(callee)
            frontier = nxt

    roots = [n for n in _ENTRY_NAMES if n in methods]
    for n in roots:
        chains[n] = [n]
    bfs(roots)
    extra = [n for n in methods
             if n.startswith(_ENTRY_PREFIXES) and n not in chains]
    for n in extra:
        chains[n] = [n]
    bfs(extra)
    return chains


def check(tree: ast.Module, ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = {n.name: _MethodInfo(n) for n in cls.body
                   if isinstance(n, FuncNode)}
        # serving-scheduler shape only: a bare `step` (optimizers etc.)
        # is not a dispatch pipeline
        if "step" not in methods or not any(
                m.startswith(("_dispatch", "_collect"))
                for m in methods):
            continue
        for mi in methods.values():
            mi.collect_calls()
        attrs = _attr_fixpoint(methods)
        for name, chain in _reachable(methods).items():
            mi = methods[name]
            tt = _Taint(_local_taint(mi.node, attrs), attrs)
            findings.extend(_scan_sinks(
                mi.node, tt, ctx, f"{cls.name}.{name}",
                " -> ".join(chain)))
    return findings


def _scan_sinks(fn_node, tt: _Taint, ctx: FileContext, qual: str,
                chain: str) -> List[Finding]:
    out: List[Finding] = []
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Call):
            head = dotted(sub.func)
            if head in _SINK_WHOLE and sub.args and \
                    tt.level(sub.args[0]) >= 1:
                out.append(Finding(
                    ctx.path, sub.lineno, "FC301",
                    f"`{head}` on a device value inside the serving "
                    f"hot path blocks the host on the device; keep "
                    f"syncs at the designed collection points", qual,
                    chain))
            elif head in _SINK_CASTS and sub.args and \
                    tt.level(sub.args[0]) >= 2:
                out.append(Finding(
                    ctx.path, sub.lineno, "FC301",
                    f"`{head}()` on a device value inside the serving "
                    f"hot path forces a blocking transfer", qual,
                    chain))
            elif isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in _SINK_METHODS and \
                    tt.level(sub.func.value) >= 2:
                out.append(Finding(
                    ctx.path, sub.lineno, "FC301",
                    f"`.{sub.func.attr}()` on a device value inside "
                    f"the serving hot path blocks the host", qual,
                    chain))
        elif isinstance(sub, (ast.If, ast.While)):
            # implicit __bool__ of a device ARRAY (`if x:`); container
            # truthiness (`if self._inflight:`) is host-side and fine
            t = sub.test
            if isinstance(t, (ast.Name, ast.Attribute, ast.Subscript)) \
                    and tt.level(t) >= 2:
                out.append(Finding(
                    ctx.path, sub.lineno, "FC301",
                    "implicit `bool()` of a device value (`if x:`) "
                    "inside the serving hot path is a hidden blocking "
                    "sync", qual, chain))
    return out


def setup(register):
    register("host_sync", check, {
        "FC301": "blocking host sync on a device value in the hot path",
    })
