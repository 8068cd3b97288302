"""The graftlint rule catalog — one rule per hazard class this repo has
actually hit (ISSUE 4 / CHANGES.md r6), each with the precision posture
of a CI gate: prefer missing a hazard over crying wolf, because every
finding either blocks a merge or must be audited into the baseline.

GL001 key-reuse            same PRNG key consumed twice / used after split
GL002 host-sync            .item()/float()/np.* on values inside traced code
GL003 donation-after-use   a donated argument read after the donating call
GL004 impure-jit           print/logkv/global/attr mutation under trace
GL005 recompile-hazard     jit built per iteration; shape-derived scalars
                           or f-strings flowing into jitted args
GL006 raw-shard-map        jax.experimental.shard_map / check_rep= used
                           directly instead of utils/jax_compat
GL007 host-sync-in-loop    float()/np.asarray/.item() on a jitted step's
                           output inside the outer (untraced) training
                           loop — a per-step host sync that defeats async
                           dispatch (dispatch_lag)
GL008 hand-wired-sharding  NamedSharding constructed (or a PartitionSpec
                           passed directly as a sharding) outside the
                           partition engine — sharding belongs in rule
                           tables (parallel/partition.py), not call sites
GL009 ad-hoc-timing        a raw time.time()/perf_counter()/monotonic()
                           delta booked straight into a metric sink
                           (logkv*, or += into a metrics mapping) outside
                           utils/perf.py and obs/ — wall-time accounting
                           belongs to the perf/obs abstractions
                           (StallBreakdown, GoodputTracker, ServingTracker,
                           obs.trace spans/Stopwatch), where one owner
                           keeps the trace and the ledgers consistent
GL010 unattributed-flops   a FLOPs/MFU figure computed from raw numeric
                           constants (a literal inside a * / / **
                           expression bound to a flops/mfu/fpt name or
                           key) outside utils/perf.py and obs/ledger.py —
                           FLOP accounting has two owners so every MFU
                           figure in the repo shares one numerator with
                           the roofline cost ledger; derive through
                           transformer_train_flops_per_token /
                           roofline_attribution
GL011 cross-module-key-reuse  the same PRNG key flowing into two
                           (transitively proven) key-consuming callees,
                           consumed after a split across a call
                           boundary, or consumed by a callee every loop
                           iteration without rebinding — the reuse
                           GL001 cannot see because the consumers live
                           behind calls (graph-only rule)
GL012 stray-pallas-call    pl.pallas_call outside ops/ — kernels live
                           behind the ops/ dispatch seams (auto/forced
                           impl knobs, interpret fallback, layout
                           contracts); a call site elsewhere bypasses
                           dispatch, fallback AND the byte accounting

Interprocedural halves (callgraph.py, ISSUE 15): GL002, GL003, GL005
and GL007 each carry a ``check_graph`` in addition to their per-module
``check`` — tracedness, donation liveness, and static-argnum facts flow
across call and module boundaries through the whole-program summary
fixpoint, turning the three audited blind spots (transitive host syncs,
cross-module donation-after-use, distant static_argnums) from
heuristics into proofs. Unknown callees widen to "don't know": the
graph half only reports what the whole chain proves.
"""

from __future__ import annotations

import ast
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

from . import callgraph
from .core import Finding, Module, Rule, register
from .dataflow import field_path, path_prefix_of, paths_conflict

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While)


def _calls_in(node: ast.AST) -> Iterator[ast.Call]:
    """Call nodes in an expression/statement, NOT descending into nested
    function definitions (those are separate scopes/contexts)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(node))
    if isinstance(node, ast.Call):
        yield node
    while stack:
        n = stack.pop()
        if isinstance(n, _FUNC_NODES):
            continue
        if isinstance(n, ast.Call):
            yield n
        stack.extend(ast.iter_child_nodes(n))


def _terminates(stmts: List[ast.stmt]) -> bool:
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


def _shallow_nodes(stmt: ast.stmt) -> Iterator[ast.AST]:
    """Nodes belonging to THIS statement only: header expressions and
    value subtrees, not nested statements (a flattened walk visits those
    on their own) and not nested function bodies."""
    stack: List[ast.AST] = [stmt]
    while stack:
        n = stack.pop()
        yield n
        for c in ast.iter_child_nodes(n):
            if isinstance(c, ast.stmt) or isinstance(c, _FUNC_NODES):
                continue
            stack.append(c)


# --------------------------------------------------------------------- GL001


class _KeyState:
    __slots__ = ("uses", "split", "from_param")

    def __init__(self, from_param: bool = False):
        self.uses = 0
        self.split = False
        self.from_param = from_param

    def copy(self) -> "_KeyState":
        st = _KeyState(self.from_param)
        st.uses, st.split = self.uses, self.split
        return st


# jax.random members that DERIVE keys rather than consuming entropy
# (one owner: callgraph.py shares these tables with the graph pass)
_KEY_DERIVERS = callgraph.KEY_DERIVERS
# callables through which passing a key is not a (countable) consumption
_KEY_TRANSPARENT = {"jax.eval_shape", "jax.device_put", "jax.tree_util.tree_map",
                    "jax.tree.map", "jax.block_until_ready", "len", "print",
                    "isinstance", "type", "repr", "str", "jax.ShapeDtypeStruct"}
_is_key_param = callgraph.is_key_param


@register
class KeyReuse(Rule):
    """GL001: the same PRNG key consumed by two samplers, consumed after
    ``jax.random.split``, or consumed inside a loop without per-iteration
    rebinding — all three produce silently correlated randomness."""

    code = "GL001-key-reuse"
    description = ("PRNG key reused: each key must reach exactly one "
                   "consumer; derive fresh keys with split/fold_in")

    def check(self, module: Module) -> Iterator[Finding]:
        self._out: List[Finding] = []
        self._mod = module
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                state: Dict[str, _KeyState] = {}
                args = node.args
                for a in (args.posonlyargs + args.args + args.kwonlyargs):
                    if _is_key_param(a.arg):
                        state[a.arg] = _KeyState(from_param=True)
                self._walk(node.body, state, loop_events=None)
        yield from self._out

    # -- state machinery

    def _report(self, node: ast.AST, msg: str) -> None:
        self._out.append(self._mod.finding(self, node, msg))

    def _consume_calls(self, stmt: ast.AST, state: Dict[str, _KeyState],
                       loop_events: Optional[List[Tuple[str, str]]]) -> None:
        for call in _calls_in(stmt):
            fn = self._mod.resolve(call.func)
            key_args = [a for a in list(call.args)
                        + [k.value for k in call.keywords]
                        if isinstance(a, ast.Name) and a.id in state]
            if not key_args:
                continue
            if fn and fn.startswith("jax.random."):
                member = fn.rsplit(".", 1)[1]
                if member in _KEY_DERIVERS:
                    continue
                for a in key_args:
                    st = state[a.id]
                    if member == "split":
                        if st.split:
                            self._report(a, f"key '{a.id}' split twice — "
                                            "each split consumes the key")
                        elif st.uses:
                            self._report(a, f"key '{a.id}' split after "
                                            "already being consumed")
                        st.split = True
                    else:
                        self._use(a, st, loop_events)
            elif fn in _KEY_TRANSPARENT:
                continue
            else:
                # arbitrary call: counts only for keys this scope derived
                # itself (param-named heuristics would false-positive on
                # non-key 'key' variables reaching helper calls)
                for a in key_args:
                    st = state[a.id]
                    if not st.from_param:
                        self._use(a, st, loop_events)

    def _use(self, name_node: ast.Name, st: _KeyState,
             loop_events: Optional[List[Tuple[str, str]]]) -> None:
        if st.split:
            self._report(name_node, f"key '{name_node.id}' used after "
                                    "split — use one of the split results")
        elif st.uses >= 1:
            self._report(name_node, f"key '{name_node.id}' consumed more "
                                    "than once — derive per-consumer keys "
                                    "with jax.random.split/fold_in")
        st.uses += 1
        if loop_events is not None:
            loop_events.append(("use", name_node.id))

    def _rebind(self, target: ast.AST, value: Optional[ast.AST],
                state: Dict[str, _KeyState],
                loop_events: Optional[List[Tuple[str, str]]]) -> None:
        fresh = False
        if isinstance(value, ast.Call):
            fn = self._mod.resolve(value.func)
            if fn and fn.startswith("jax.random."):
                # only key-DERIVING members produce keys; a sampler's
                # output (jax.random.normal(...)) is data, not a key
                member = fn.rsplit(".", 1)[1]
                fresh = member in _KEY_DERIVERS or member == "split"
        names: List[str] = []
        if isinstance(target, ast.Name):
            names = [target.id]
        elif isinstance(target, (ast.Tuple, ast.List)):
            names = [e.id for e in target.elts if isinstance(e, ast.Name)]
        for n in names:
            if fresh:
                state[n] = _KeyState()
                if loop_events is not None:
                    loop_events.append(("rebind", n))
            else:
                state.pop(n, None)

    def _walk(self, stmts: List[ast.stmt], state: Dict[str, _KeyState],
              loop_events: Optional[List[Tuple[str, str]]]) -> None:
        for s in stmts:
            if isinstance(s, _FUNC_NODES[:2]) or isinstance(s, ast.ClassDef):
                continue  # separate scope
            if isinstance(s, ast.If):
                self._consume_calls(s.test, state, loop_events)
                branches = []
                for body in (s.body, s.orelse):
                    st = {k: v.copy() for k, v in state.items()}
                    self._walk(body, st, loop_events)
                    if not _terminates(body):
                        branches.append(st)
                self._merge(state, branches)
            elif isinstance(s, _LOOP_NODES):
                if isinstance(s, (ast.For, ast.AsyncFor)):
                    self._consume_calls(s.iter, state, loop_events)
                    self._rebind(s.target, None, state, loop_events)
                else:
                    self._consume_calls(s.test, state, loop_events)
                pre = set(state)
                events: List[Tuple[str, str]] = []
                self._walk(s.body, state, events)
                used = {n for kind, n in events if kind == "use"}
                rebound = {n for kind, n in events if kind == "rebind"}
                for n in sorted(used & pre - rebound):
                    self._report(s, f"key '{n}' from outside the loop is "
                                    "consumed every iteration without "
                                    "rebinding (same randomness each pass)")
                self._walk(s.orelse, state, loop_events)
            elif isinstance(s, (ast.With, ast.AsyncWith)):
                for item in s.items:
                    self._consume_calls(item.context_expr, state, loop_events)
                self._walk(s.body, state, loop_events)
            elif isinstance(s, ast.Try):
                # try body on the live state (it's the path that runs);
                # handlers/orelse on throwaway copies — consuming the whole
                # Try subtree up front would double-count the body's uses
                self._walk(s.body, state, loop_events)
                for body in [h.body for h in s.handlers] + [s.orelse]:
                    st = {k: v.copy() for k, v in state.items()}
                    self._walk(body, st, None)
                self._walk(s.finalbody, state, loop_events)
            elif isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = getattr(s, "value", None)
                if value is not None:
                    self._consume_calls(value, state, loop_events)
                targets = (s.targets if isinstance(s, ast.Assign)
                           else [s.target])
                for t in targets:
                    self._rebind(t, value, state, loop_events)
            else:
                self._consume_calls(s, state, loop_events)

    @staticmethod
    def _merge(state: Dict[str, _KeyState],
               branches: List[Dict[str, _KeyState]]) -> None:
        if not branches:
            return  # both branches terminated: keep pre-branch state
        for name in list(state):
            alive = [b[name] for b in branches if name in b]
            if len(alive) < len(branches):
                state.pop(name)  # rebound to a non-key somewhere
                continue
            st = state[name]
            st.uses = max(b.uses for b in alive)
            st.split = any(b.split for b in alive)
        for b in branches:
            for name, st in b.items():
                if name not in state:
                    state[name] = st.copy()


# --------------------------------------------------------------------- GL002

# numpy members that force (or silently constant-fold) a host round-trip
# when handed a tracer; shape/constant builders (arange/zeros/linspace...)
# stay legal — they consume static python values. (Shared table:
# callgraph.py uses the same set for the transitive half.)
_SYNC_NP = callgraph.SYNC_NP


@register
class HostSync(Rule):
    """GL002: device->host synchronization inside traced code —
    ``.item()``, ``float()/int()/bool()`` on non-literals, numpy ops, and
    explicit ``device_get``/``block_until_ready`` all either fail at trace
    time or (worse) silently freeze a traced value at trace time.

    Graph half (PROVEN, not lexical): a helper whose parameter-rooted
    host sync is reached from any traced context through an
    interprocedurally resolved call chain — across modules — is flagged
    at the sync site with the traced caller as witness."""

    code = "GL002-host-sync"
    description = ("host sync inside jit/scan-traced code (or in a "
                   "helper any traced context reaches transitively): "
                   ".item(), float()/int(), np.*, device_get, "
                   "block_until_ready")

    def check_graph(self, graph: Any) -> Iterator[Finding]:
        return graph.iter_transitive_host_syncs(self)

    def check(self, module: Module) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or not module.in_traced(node):
                continue
            func = node.func
            fn = module.resolve(func)
            if isinstance(func, ast.Attribute) and func.attr == "item" \
                    and not node.args:
                yield module.finding(self, node,
                                     ".item() forces a device->host sync "
                                     "(trace error under jit)")
            elif isinstance(func, ast.Name) and func.id in ("float", "int",
                                                            "bool") \
                    and len(node.args) == 1 \
                    and not isinstance(node.args[0], ast.Constant):
                yield module.finding(
                    self, node,
                    f"{func.id}() on a possibly-traced value blocks on the "
                    "device (or freezes a trace-time constant); keep it a "
                    "device scalar or hoist the conversion out of the "
                    "traced function")
            elif fn and fn.startswith("numpy.") \
                    and fn.split(".")[-1] in _SYNC_NP:
                yield module.finding(
                    self, node,
                    f"numpy call '{fn}' inside traced code syncs or "
                    "constant-folds at trace time; use jax.numpy")
            elif fn == "jax.device_get":
                yield module.finding(self, node,
                                     "jax.device_get inside traced code")
            elif isinstance(func, ast.Attribute) \
                    and func.attr == "block_until_ready":
                yield module.finding(self, node,
                                     "block_until_ready inside traced code")


# --------------------------------------------------------------------- GL003


@register
class DonationAfterUse(Rule):
    """GL003: an argument donated to a jitted call is read afterwards.
    The donated buffer is dead (or worse, aliased into the output — the
    r6 heap-corruption class when combined with cache-deserialized
    executables); every read after the donating call is a use of freed
    memory the runtime may or may not catch."""

    code = "GL003-donation-after-use"
    description = ("argument donated via donate_argnums is read after "
                   "the donating call — including donors imported from "
                   "another module or helpers that transitively donate")

    def check_graph(self, graph: Any) -> Iterator[Finding]:
        # cross-module donors (imported jitted bindings, helpers that
        # transitively donate a parameter) — the r6 orbax-restore shape
        return graph.iter_cross_module_donations(self)

    def check(self, module: Module) -> Iterator[Finding]:
        if not module.donations:
            return
        scopes: List[List[ast.stmt]] = [module.tree.body]
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node.body)
        for body in scopes:
            yield from self._scan_scope(module, body)

    def _scan_scope(self, module: Module,
                    body: List[ast.stmt]) -> Iterator[Finding]:
        # linear source-order walk of the whole scope (branch-insensitive:
        # donation sites are rare enough that simplicity wins)
        stmts: List[ast.stmt] = []

        def flatten(ss: List[ast.stmt]) -> None:
            for s in ss:
                if isinstance(s, _FUNC_NODES[:2]) or isinstance(s, ast.ClassDef):
                    continue
                stmts.append(s)
                for field in ("body", "orelse", "finalbody"):
                    flatten(getattr(s, field, []) or [])
                for h in getattr(s, "handlers", []) or []:
                    flatten(h.body)

        flatten(body)
        pending: Dict[str, ast.AST] = {}
        for s in stmts:
            live = {t for t in pending}
            if live:
                # maximal canonical read paths only: once state['params']
                # is recorded, its inner Name `state` is not a separate
                # read (otherwise a sibling-field read would conflict
                # through its container root)
                skip: Set[int] = set()
                for n in _shallow_nodes(s):
                    if id(n) in skip:
                        continue
                    if not isinstance(
                            n, (ast.Name, ast.Attribute, ast.Subscript)):
                        continue
                    if not isinstance(getattr(n, "ctx", None), ast.Load):
                        continue
                    text = field_path(n)
                    if text is not None:
                        for c in ast.walk(n):
                            skip.add(id(c))
                    elif isinstance(n, ast.Subscript):
                        # dynamic index: the base container is read;
                        # which field stays unproven, so only the base
                        # chain participates (its slice is still walked)
                        text = field_path(n.value)
                        if text is None:
                            continue
                        for c in ast.walk(n.value):
                            skip.add(id(c))
                    else:
                        continue
                    for donated in sorted(live):
                        # component-wise both ways: reading the dead
                        # field, a sub-path of it, or the whole
                        # container that still holds it; a SIBLING
                        # field (state['opt'] vs state['params'])
                        # conflicts with neither
                        if paths_conflict(text, donated):
                            yield module.finding(
                                self, n,
                                f"'{donated}' was donated to a jitted call "
                                "above — its buffer is dead; reading it is "
                                "use-after-free (copy it first or use the "
                                "call's result)")
                            live.discard(donated)
            # rebinds clear; new donations arm
            targets: List[ast.AST] = []
            if isinstance(s, ast.Assign):
                targets = list(s.targets)
            elif isinstance(s, (ast.AnnAssign, ast.AugAssign)):
                targets = [s.target]
            target_texts: Set[str] = set()
            for t in targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                for e in elts:
                    tp = field_path(e)
                    if tp is not None:
                        target_texts.add(tp)
            for call in (n for n in _shallow_nodes(s)
                         if isinstance(n, ast.Call)):
                try:
                    callee = ast.unparse(call.func)
                except Exception:  # pragma: no cover - defensive
                    continue
                positions = module.donations.get(callee)
                if not positions:
                    continue
                for p in positions:
                    if p < len(call.args):
                        donated = field_path(call.args[p])
                        if donated is not None \
                                and donated not in target_texts:
                            pending[donated] = call
            for t in target_texts:
                # assigning the container kills its donated fields too
                for d in list(pending):
                    if path_prefix_of(t, d):
                        pending.pop(d)


# --------------------------------------------------------------------- GL004


@register
class ImpureJit(Rule):
    """GL004: side effects inside traced code run ONCE at trace time, not
    per step — prints vanish, metrics log a single stale value, attribute
    and global mutation desyncs from the compiled computation."""

    code = "GL004-impure-jit"
    description = ("side effect under jit/scan: print, logkv/logging, "
                   "global/nonlocal, attribute mutation")

    def check(self, module: Module) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not module.in_traced(node):
                continue
            if isinstance(node, ast.Call):
                func = node.func
                fn = module.resolve(func)
                if isinstance(func, ast.Name) and func.id == "print":
                    yield module.finding(
                        self, node, "print() under trace runs once at "
                        "trace time — use jax.debug.print")
                elif isinstance(func, ast.Attribute) \
                        and func.attr.startswith("logkv"):
                    yield module.finding(
                        self, node, "metric logging under trace records a "
                        "tracer once, not a value per step — log outside "
                        "the jitted step")
                elif fn and fn.startswith("logging."):
                    yield module.finding(
                        self, node, "logging call under trace runs once "
                        "at trace time")
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                yield module.finding(
                    self, node, f"{type(node).__name__.lower()} statement "
                    "under trace: mutation will not re-run per step")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Attribute):
                        yield module.finding(
                            self, t, f"attribute mutation "
                            f"'{ast.unparse(t)} = ...' under trace happens "
                            "once at trace time — return the value instead")


# --------------------------------------------------------------------- GL005


@register
class RecompileHazard(Rule):
    """GL005: patterns that defeat jit's compile cache — a fresh jit
    wrapper built per loop iteration, and shape-derived Python scalars
    (``len(x)``, ``x.shape``) or per-step-varying f-strings flowing into
    a jitted call's traced arguments (each new value = a full retrace;
    the r6 hidden step-2 recompile class).

    The rule is static-argnum aware in BOTH halves: an argument the
    ``jax.jit``/``functools.partial`` site declares static (by position
    or name) is supposed to vary — no finding. The graph half resolves
    jitted bindings imported from other modules (including through
    re-exports and partial chains), closing the "static_argnums declared
    far from the call site" blind spot in both directions: a distant
    declaration suppresses the false positive, and a distant jitted
    binding called with a hazard argument is now caught at all."""

    code = "GL005-recompile-hazard"
    description = ("recompile hazard: jit built inside a loop, or "
                   "len()/.shape/f-string values passed NON-STATIC into "
                   "a jitted binding (local or imported)")

    def check(self, module: Module) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if module._wrapper_name(node.func) == "jax.jit":
                cur = module.parent.get(node)
                while cur is not None and not isinstance(cur, _FUNC_NODES):
                    if isinstance(cur, _LOOP_NODES):
                        yield module.finding(
                            self, node, "jax.jit called inside a loop "
                            "builds a fresh wrapper (and cache entry) per "
                            "iteration — hoist the jit out of the loop")
                        break
                    cur = module.parent.get(cur)
                continue
            try:
                callee = ast.unparse(node.func)
            except Exception:  # pragma: no cover - defensive
                continue
            if callee not in module.jitted_bindings:
                continue
            info = module.jit_info.get(callee, {})
            argnums = {int(x) for x in info.get("static_argnums", ())}
            argnames = set(info.get("static_argnames", ()))
            wrapped_params = self._wrapped_params(module, info)
            for i, arg in enumerate(node.args):
                hazard = self._scalar_hazard(arg)
                if not hazard:
                    continue
                pname = (wrapped_params[i]
                         if i < len(wrapped_params) else None)
                if i in argnums or (pname and pname in argnames):
                    continue  # declared static: supposed to vary
                yield self._hazard(module, arg, hazard, callee)
            for kw in node.keywords:
                if kw.arg is None:
                    continue
                hazard = self._scalar_hazard(kw.value)
                if not hazard:
                    continue
                if kw.arg in argnames or (
                        kw.arg in wrapped_params
                        and wrapped_params.index(kw.arg) in argnums):
                    continue
                yield self._hazard(module, kw.value, hazard, callee)

    def check_graph(self, graph: Any) -> Iterator[Finding]:
        # jitted bindings resolved across module boundaries, with the
        # distant static_argnums/static_argnames honored
        return graph.iter_distant_static_hazards(self)

    def _hazard(self, module: Module, arg: ast.AST, hazard: str,
                callee: str) -> Finding:
        return module.finding(
            self, arg, f"{hazard} flows into jitted call "
            f"'{callee}' as a traced argument — every new "
            "value retraces and recompiles; mark it static "
            "(static_argnums) or derive it inside the jit")

    @staticmethod
    def _wrapped_params(module: Module, info: dict) -> List[str]:
        """Positional parameter names of the function the binding
        wraps, when it is a plain local def (maps static_argnames to
        positions and vice versa); [] when unknown."""
        target = info.get("target")
        if not target or "." in target:
            return []
        defs = module.defs_by_name.get(target, ())
        for d in defs:
            a = d.args
            return [p.arg for p in a.posonlyargs + a.args]
        return []

    _scalar_hazard = staticmethod(callgraph._scalar_hazard)


# --------------------------------------------------------------------- GL006

_COMPAT_EXEMPT = "utils/jax_compat.py"
_RAW_SHARD_MAP = "jax.experimental.shard_map"


@register
class RawShardMap(Rule):
    """GL006: shard_map imported/used from jax.experimental (or a raw
    ``check_rep=`` kwarg) instead of utils/jax_compat — the one spelling
    that works on both the jax>=0.6 stable API and this image's 0.4.x
    (CHANGES.md r6: the raw import ImportError'd every ring/pipeline test
    at seed)."""

    code = "GL006-raw-shard-map"
    description = ("raw jax.experimental.shard_map / check_rep= bypasses "
                   "utils/jax_compat.py")

    def check(self, module: Module) -> Iterator[Finding]:
        if module.path.replace("\\", "/").endswith(_COMPAT_EXEMPT):
            return
        suggestion = ("import shard_map from "
                      "distributed_pipeline_tpu.utils.jax_compat (version "
                      "bridge for jax 0.4.x check_rep vs >=0.6 check_vma)")
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod.startswith(_RAW_SHARD_MAP) or (
                        mod == "jax.experimental"
                        and any(a.name == "shard_map" for a in node.names)):
                    yield module.finding(
                        self, node,
                        f"raw import from {_RAW_SHARD_MAP} — {suggestion}")
            elif isinstance(node, ast.Attribute) and not isinstance(
                    module.parent.get(node), ast.Attribute):
                fn = module.resolve(node)
                if fn and fn.startswith(_RAW_SHARD_MAP):
                    yield module.finding(
                        self, node,
                        f"direct use of {fn} — {suggestion}")
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg == "check_rep":
                        yield module.finding(
                            self, node,
                            "check_rep= is the pre-0.6 spelling — call "
                            "through utils/jax_compat.shard_map with "
                            "check_vma= instead")


# --------------------------------------------------------------------- GL007

# conversions that block the host on an in-flight device value
# (shared tables: callgraph.py uses the same sets for the graph half)
_GL007_NP_BLOCKERS = callgraph.NP_BLOCKERS
_GL007_BUILTINS = callgraph.BLOCKING_BUILTINS
# method names whose call result is (very likely) a jitted step's output:
# the trainer's own loop surface plus the conventional step-fn spellings
_GL007_STEP_ATTRS = callgraph.STEP_ATTRS


def _root_name(node: ast.AST) -> Optional[str]:
    """Base Name of a Subscript/Attribute chain (``m["loss"]`` -> ``m``,
    ``out.loss`` -> ``out``); None for anything not rooted at a plain
    name (so ``float(jax.device_get(m["loss"]))`` — the SANCTIONED
    explicit-fetch spelling — never matches)."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


@register
class HostSyncInLoop(Rule):
    """GL007: a blocking conversion (``float()``/``int()``,
    ``np.asarray``/``np.array``, ``.item()``) applied to a jitted step's
    output INSIDE the outer training loop. Unlike GL002 this code is not
    traced — it runs, and it quietly serializes the pipeline: every
    iteration the host stalls on the step it just dispatched, so async
    dispatch (``dispatch_lag``) and device prefetch buy nothing. The
    fix is to keep metrics as device scalars in the loop (the logger
    fetches them in one batch at dump time) or fetch explicitly with
    ``jax.device_get`` outside the loop."""

    code = "GL007-host-sync-in-loop"
    description = ("blocking conversion (float()/np.asarray/.item()) of a "
                   "jitted step's output inside the outer training loop "
                   "— directly or through a helper that transitively "
                   "blocks on its argument — serializes async dispatch")

    def check_graph(self, graph: Any) -> Iterator[Finding]:
        # a loop handing a step output to a helper that (transitively)
        # float()s/.item()s it — the hop the lexical rule cannot see
        return graph.iter_loop_blocking_calls(self)

    def check(self, module: Module) -> Iterator[Finding]:
        reported: Set[int] = set()
        for loop in ast.walk(module.tree):
            if not isinstance(loop, _LOOP_NODES) or module.in_traced(loop):
                continue
            step_names = self._step_output_names(module, loop)
            for node in ast.walk(loop):
                if id(node) in reported or not isinstance(node, ast.Call):
                    continue
                hit = self._blocking_conversion(module, node, step_names)
                if hit:
                    reported.add(id(node))
                    yield module.finding(
                        self, node,
                        f"{hit} blocks the host on the in-flight step "
                        "every loop iteration — a per-step sync that "
                        "defeats async dispatch (dispatch_lag) and device "
                        "prefetch; keep it a device scalar (the logger "
                        "batches the fetch at dump time) or device_get it "
                        "once outside the loop")

    @staticmethod
    def _is_step_call(module: Module, call: ast.Call) -> bool:
        func = call.func
        if isinstance(func, ast.Attribute) \
                and func.attr in _GL007_STEP_ATTRS:
            return True
        try:
            callee = ast.unparse(func)
        except Exception:  # pragma: no cover - defensive
            return False
        return callee in module.jitted_bindings

    def _step_output_names(self, module: Module,
                           loop: ast.AST) -> Set[str]:
        """Names assigned anywhere in the loop body from a step-ish call
        (``m = loop.run_step(...)``, ``out = compiled(...)`` for a known
        jitted binding) — the values whose conversion blocks."""
        names: Set[str] = set()
        for node in ast.walk(loop):
            if not isinstance(node, ast.Assign) \
                    or not isinstance(node.value, ast.Call):
                continue
            if not self._is_step_call(module, node.value):
                continue
            for t in node.targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                for e in elts:
                    if isinstance(e, ast.Name):
                        names.add(e.id)
        return names

    def _blocking_conversion(self, module: Module, call: ast.Call,
                             step_names: Set[str]) -> Optional[str]:
        """Description of the blocking conversion this call performs on a
        step output, or None."""
        func = call.func
        # (step_output).item() / m["loss"].item()
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not call.args:
            if self._operand_is_step_output(module, func.value, step_names):
                return ".item() on a step output"
            return None
        if len(call.args) != 1:
            return None
        operand = call.args[0]
        if not self._operand_is_step_output(module, operand, step_names):
            return None
        if isinstance(func, ast.Name) and func.id in _GL007_BUILTINS:
            return f"{func.id}() on a step output"
        fn = module.resolve(func)
        if fn in _GL007_NP_BLOCKERS:
            return f"{fn} on a step output"
        return None

    def _operand_is_step_output(self, module: Module, operand: ast.AST,
                                step_names: Set[str]) -> bool:
        root = _root_name(operand)
        if root is not None:
            return root in step_names
        # direct form: float(loop.run_step(...)["loss"])
        node = operand
        while isinstance(node, (ast.Subscript, ast.Attribute)):
            node = node.value
        return isinstance(node, ast.Call) and self._is_step_call(module,
                                                                 node)


# --------------------------------------------------------------------- GL008

# The partition engine: the only modules allowed to BIND specs to meshes.
# parallel/partition.py is the rule engine itself; parallel/sharding.py is
# its compat shim (flax logical metadata + the batch/IO helpers).
_GL008_ENGINE = ("parallel/partition.py", "parallel/sharding.py")
_GL008_NAMED_SHARDING = "jax.sharding.NamedSharding"
_GL008_PSPEC = "jax.sharding.PartitionSpec"
# kwarg names through which a bare PartitionSpec acts as a sharding at the
# call site (jit/device_put surfaces). shard_map's in_specs/out_specs are
# deliberately NOT here: those are engine-level SPMD plumbing (pipeline /
# ring internals), not a parameter-sharding decision.
_GL008_SHARDING_KWARGS = {"in_shardings", "out_shardings", "out_sharding",
                          "sharding"}


@register
class HandWiredSharding(Rule):
    """GL008: a ``NamedSharding`` constructed — or a ``PartitionSpec``
    passed directly as a sharding — outside the partition engine. Hand-
    wired sharding trees are exactly what the regex-rule engine
    (parallel/partition.py: ``match_partition_rules`` + per-model tables)
    replaced: a spec decided at a call site is invisible to the rule
    tables, drifts from them silently, and puts the next model back to
    editing engine code. Declare a rule (or use the engine/sharding
    helpers: ``replicated``, ``batch_shardings``, ``resolve_shardings``,
    ``make_shard_and_gather_fns``) instead. Bare ``PartitionSpec``
    construction stays legal — rule tables and shard_map specs are made
    of them; only using one AS a sharding (device_put target,
    in_/out_shardings=) is flagged."""

    code = "GL008-hand-wired-sharding"
    description = ("NamedSharding/PartitionSpec hand-wired as a sharding "
                   "outside parallel/partition.py|sharding.py — declare a "
                   "partition rule or use the sharding helpers")

    def check(self, module: Module) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if any(path.endswith(e) for e in _GL008_ENGINE):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = module.resolve(node.func)
            if fn == _GL008_NAMED_SHARDING:
                yield module.finding(
                    self, node,
                    "NamedSharding constructed outside the partition "
                    "engine — declare a partition rule "
                    "(parallel/partition.py) or use the sharding helpers "
                    "(replicated/batch_shardings/resolve_shardings)")
            elif fn == _GL008_PSPEC and self._used_as_sharding(module,
                                                              node):
                yield module.finding(
                    self, node,
                    "PartitionSpec passed directly as a sharding — bind "
                    "specs to meshes through the partition engine "
                    "(resolve_shardings/make_shard_and_gather_fns), not "
                    "at the call site")

    @staticmethod
    def _used_as_sharding(module: Module, node: ast.Call) -> bool:
        parent = module.parent.get(node)
        if isinstance(parent, ast.keyword) \
                and parent.arg in _GL008_SHARDING_KWARGS:
            return True
        if isinstance(parent, ast.keyword) and parent.arg == "device":
            # device= is generic; only a device_put target is a sharding
            grand = module.parent.get(parent)
            return isinstance(grand, ast.Call) \
                and module.resolve(grand.func) == "jax.device_put"
        if isinstance(parent, ast.Call):
            fn = module.resolve(parent.func)
            if fn in ("jax.device_put", "jax.lax.with_sharding_constraint") \
                    and len(parent.args) >= 2 and parent.args[1] is node:
                return True
        return False


# --------------------------------------------------------------------- GL009

# The sanctioned owners of wall-time deltas that become metrics. perf.py
# holds the training-side accounting (StallBreakdown/GoodputTracker/
# StepTimer/EventStats); everything under obs/ holds the tracing layer
# (spans, Stopwatch) — both are WHERE the subtraction is supposed to live.
_GL009_EXEMPT_SUFFIXES = ("utils/perf.py",)
_GL009_EXEMPT_DIRS = ("/obs/",)
_GL009_CLOCKS = {"time.time", "time.perf_counter", "time.monotonic"}


def _gl009_exempt(path: str) -> bool:
    p = path.replace("\\", "/")
    return (any(p.endswith(s) for s in _GL009_EXEMPT_SUFFIXES)
            or any(d in p for d in _GL009_EXEMPT_DIRS))


@register
class AdHocTiming(Rule):
    """GL009: a raw clock delta (``time.time()``/``perf_counter()``/
    ``monotonic()`` subtraction) booked straight into a metric sink —
    a ``logkv*`` call, or ``+=`` into a metrics mapping entry — outside
    ``utils/perf.py``/``obs/``. Scattered ad-hoc timing is exactly what
    made "where did the wall time go" unanswerable before the goodput
    ledger: each such delta is a category no fold accounts for, invisible
    to the trace timeline, and (for ``time.time()``) vulnerable to clock
    steps. Book the window through the owning abstraction instead
    (StallBreakdown/GoodputTracker/ServingTracker ``add``/``timed``, an
    ``obs.trace`` span, or ``obs.trace.Stopwatch`` when a raw number is
    genuinely all that's needed). Computing a delta for control flow or
    a result dict stays legal — only the direct delta->metric-sink flow
    is flagged, so the rule gates without drowning the baseline."""

    code = "GL009-ad-hoc-timing"
    description = ("raw time.time()/perf_counter() delta booked into a "
                   "metric sink outside utils/perf.py|obs/ — use the "
                   "perf/obs timing abstractions")

    def check(self, module: Module) -> Iterator[Finding]:
        if _gl009_exempt(module.path):
            return
        scopes: List[List[ast.stmt]] = [module.tree.body]
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node.body)
        for body in scopes:
            yield from self._scan_scope(module, body)

    # -- helpers

    def _is_clock_call(self, module: Module, node: ast.AST) -> bool:
        return isinstance(node, ast.Call) \
            and module.resolve(node.func) in _GL009_CLOCKS

    def _is_delta(self, module: Module, node: ast.AST) -> bool:
        return (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and (self._is_clock_call(module, node.left)
                     or self._is_clock_call(module, node.right)))

    def _delta_in(self, module: Module, tree: ast.AST,
                  delta_names: Set[str]) -> Optional[ast.AST]:
        """A clock-delta expression (or a name bound to one in this
        scope) inside ``tree``, not descending into nested functions."""
        stack: List[ast.AST] = [tree]
        while stack:
            n = stack.pop()
            if isinstance(n, _FUNC_NODES):
                continue
            if self._is_delta(module, n):
                return n
            if isinstance(n, ast.Name) \
                    and isinstance(getattr(n, "ctx", None), ast.Load) \
                    and n.id in delta_names:
                return n
            stack.extend(ast.iter_child_nodes(n))
        return None

    def _scan_scope(self, module: Module,
                    body: List[ast.stmt]) -> Iterator[Finding]:
        # flattened source-order walk of the scope's own statements
        # (nested defs are their own scope), like GL003
        stmts: List[ast.stmt] = []

        def flatten(ss: List[ast.stmt]) -> None:
            for s in ss:
                if isinstance(s, _FUNC_NODES[:2]) \
                        or isinstance(s, ast.ClassDef):
                    continue
                stmts.append(s)
                for field in ("body", "orelse", "finalbody"):
                    flatten(getattr(s, field, []) or [])
                for h in getattr(s, "handlers", []) or []:
                    flatten(h.body)

        flatten(body)
        delta_names: Set[str] = set()
        for s in stmts:
            # a name bound to a clock delta is a delta one hop later;
            # any other rebind clears it
            if isinstance(s, ast.Assign) and len(s.targets) == 1 \
                    and isinstance(s.targets[0], ast.Name):
                if self._is_delta(module, s.value):
                    delta_names.add(s.targets[0].id)
                else:
                    delta_names.discard(s.targets[0].id)
            # sink 1: logkv*(..., <delta>) — the logger books the raw
            # number with no category any ledger accounts for. Shallow
            # nodes only: nested statements are flattened separately.
            for call in (n for n in _shallow_nodes(s)
                         if isinstance(n, ast.Call)):
                func = call.func
                name = (func.attr if isinstance(func, ast.Attribute)
                        else func.id if isinstance(func, ast.Name)
                        else "")
                if not name.startswith("logkv"):
                    continue
                for arg in list(call.args) + [k.value for k in
                                              call.keywords]:
                    hit = self._delta_in(module, arg, delta_names)
                    if hit is not None:
                        yield module.finding(
                            self, hit,
                            "raw clock delta logged as a metric — book "
                            "the window through perf/obs (StallBreakdown/"
                            "GoodputTracker add, a trace span, or "
                            "obs.trace.Stopwatch) so the goodput fold "
                            "and the timeline account for it")
            # sink 2: metrics_map[key] += <delta> (the reference
            # logger's wall-time accumulator pattern)
            if isinstance(s, ast.AugAssign) and isinstance(s.op, ast.Add) \
                    and isinstance(s.target, ast.Subscript):
                hit = self._delta_in(module, s.value, delta_names)
                if hit is not None:
                    yield module.finding(
                        self, hit,
                        "raw clock delta accumulated into a metrics "
                        "mapping — use obs.trace.Stopwatch (or a perf "
                        "tracker) as the delta's owner")


# --------------------------------------------------------------------- GL010

# The two sanctioned owners of FLOPs/MFU arithmetic: the analytic
# numerators (utils/perf.py) and the roofline attribution (obs/ledger.py).
_GL010_EXEMPT_SUFFIXES = ("utils/perf.py", "obs/ledger.py")
_GL010_ARITH_OPS = (ast.Mult, ast.Div, ast.Pow)


def _gl010_exempt(path: str) -> bool:
    p = path.replace("\\", "/")
    return any(p.endswith(s) for s in _GL010_EXEMPT_SUFFIXES)


def _gl010_name_hit(name: str) -> bool:
    low = name.lower()
    return ("mfu" in low or "flop" in low or low == "fpt"
            or low.endswith("_fpt") or low.startswith("fpt_"))


@register
class UnattributedFlops(Rule):
    """GL010: a FLOPs/MFU figure derived from raw numeric constants —
    a literal participating in a ``*``/``/``/``**`` expression whose
    result binds to a flops/mfu/fpt-named variable, keyword, or dict
    key — outside the two sanctioned owners. Scattered ``6*N + 12*l*h*s``
    re-derivations are how the repo's MFU numbers drift apart: each
    inline copy silently disagrees with the cost ledger's. A pure call
    into the owners (``transformer_train_flops_per_token(...)``,
    ``mfu(...)``, ``roofline_attribution(...)``) — or any expression
    without literal arithmetic — stays legal, so the rule gates without
    noise."""

    code = "GL010-unattributed-flops"
    description = ("FLOPs/MFU figure computed from raw numeric constants "
                   "outside utils/perf.py|obs/ledger.py — derive it "
                   "through the perf/ledger owners")

    def check(self, module: Module) -> Iterator[Finding]:
        if _gl010_exempt(module.path):
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and _gl010_name_hit(node.targets[0].id):
                yield from self._flag(module, node.value,
                                      node.targets[0].id)
            elif isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Name) \
                    and _gl010_name_hit(node.target.id):
                yield from self._flag(module, node.value, node.target.id)
            elif isinstance(node, ast.keyword) and node.arg \
                    and _gl010_name_hit(node.arg):
                yield from self._flag(module, node.value, node.arg)
            elif isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str) \
                            and _gl010_name_hit(k.value):
                        yield from self._flag(module, v, k.value)

    def _flag(self, module: Module, expr: ast.AST,
              name: str) -> Iterator[Finding]:
        hit = self._literal_arith(expr)
        if hit is not None:
            yield module.finding(
                self, hit,
                f"{name!r} computed from raw numeric constants — FLOPs/"
                f"MFU arithmetic belongs to utils/perf.py (analytic "
                f"numerators: transformer_train_flops_per_token, "
                f"mfu) or obs/ledger.py (roofline "
                f"attribution), so every figure shares one numerator "
                f"with the cost ledger")

    @staticmethod
    def _literal_arith(expr: ast.AST) -> Optional[ast.AST]:
        """A BinOp multiplying/dividing by a numeric literal inside
        ``expr`` (not descending into nested function definitions)."""
        stack: List[ast.AST] = [expr]
        while stack:
            n = stack.pop()
            if isinstance(n, _FUNC_NODES):
                continue
            if isinstance(n, ast.BinOp) \
                    and isinstance(n.op, _GL010_ARITH_OPS):
                for side in (n.left, n.right):
                    if isinstance(side, ast.Constant) \
                            and isinstance(side.value, (int, float)) \
                            and not isinstance(side.value, bool):
                        return n
            stack.extend(ast.iter_child_nodes(n))
        return None


# --------------------------------------------------------------------- GL011


@register
class CrossModuleKeyReuse(Rule):
    """GL011: the same PRNG key flowing into two key-consuming callees
    (graph-only rule — the whole point is that the consumers live behind
    calls, often in other modules). GL001 deliberately does not count a
    key-named parameter passed to an arbitrary call — without knowing
    the callee, that would drown the report in maybes. The call graph
    removes the guesswork: a callee parameter is *proven* key-consuming
    when a ``jax.random`` sampler (or split) reaches it transitively, so
    the replay can count those calls as consumptions exactly. Flags:
    two consumptions of one key where at least one crosses a proven
    callee; consumption after ``jax.random.split`` across a call
    boundary; and a proven consumer called every loop iteration on a
    key from outside the loop without rebinding."""

    code = "GL011-cross-module-key-reuse"
    description = ("same PRNG key consumed by two (transitively proven) "
                   "key-consuming callees across call/module boundaries "
                   "— correlated randomness GL001 cannot see")

    def check_graph(self, graph: Any) -> Iterator[Finding]:
        return graph.iter_cross_module_key_reuse(self)


# --------------------------------------------------------------------- GL012


_OPS_DIR = "/ops/"
_PALLAS_ROOTS = ("jax.experimental.pallas", "jax._src.pallas")


@register
class StrayPallasCall(Rule):
    """GL012: ``pl.pallas_call`` outside ``ops/`` — kernels live behind
    the ops/ dispatch seams (``resolve_decode_impl`` auto/forced knobs,
    ``interpret=`` CPU fallback, the (8, 128) layout contracts and the
    schedule-derived HBM byte accounting). A call site anywhere else
    gets none of that: it hard-fails off-TPU, dodges the impl knob the
    configs thread through the stack, and its bytes are counted by no
    roofline."""

    code = "GL012-stray-pallas-call"
    description = ("pl.pallas_call outside ops/ bypasses the dispatch "
                   "seam, interpret fallback and byte accounting")

    def check(self, module: Module) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if _OPS_DIR in path or path.startswith("ops/"):
            return
        suggestion = ("wrap the kernel in distributed_pipeline_tpu/ops/ "
                      "behind an impl='auto'|'pallas'|'xla' dispatch "
                      "function (see ops/flash_decode.py) and call the "
                      "seam instead")
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Attribute) and not isinstance(
                    module.parent.get(node), ast.Attribute):
                fn = module.resolve(node)
                if fn and fn.startswith(_PALLAS_ROOTS) \
                        and fn.endswith(".pallas_call"):
                    yield module.finding(
                        self, node,
                        f"{fn} used outside ops/ — {suggestion}")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod.startswith(_PALLAS_ROOTS) and any(
                        a.name == "pallas_call" for a in node.names):
                    yield module.finding(
                        self, node,
                        f"pallas_call imported from {mod} outside ops/ "
                        f"— {suggestion}")


# --------------------------------------------------------------------- GL013


# duplicated from utils/perf.py (SANITIZE_REPORT_NAME) on purpose: the
# analyzer must stay importable without jax
SANITIZE_REPORT_NAME = "sanitize_report.json"


@register
class RuntimeCoverageGap(Rule):
    """GL013: the runtime sanitizer (``--sanitize``) observed a violation
    — a transfer-guard trip or a steady-state recompile — at a site the
    static pass CLEARED. The two passes audit each other: a runtime
    violation with no static finding at the same file+line means either
    a rule blind spot (file an issue, the evidence names the exact site)
    or true dynamic behavior no static pass can prove (audit it into the
    baseline with --write-baseline). Only fires in
    ``--runtime-evidence RUN_DIR`` mode; the per-module and graph passes
    yield nothing."""

    code = "GL013-runtime-coverage-gap"
    description = ("runtime sanitizer evidence (sanitize_report.json) "
                   "shows a violation at a site the static pass cleared "
                   "— a coverage gap: rule blind spot or true dynamic "
                   "behavior (only with --runtime-evidence)")

    def check(self, module: Module) -> Iterator[Finding]:
        return iter(())


_KIND_LABEL = {
    "transfer_guard": "an implicit host<->device transfer tripped the "
                      "transfer guard",
    "steady_recompile": "XLA kept compiling after steady state",
}


def runtime_evidence_findings(violations: List[Dict[str, Any]],
                              findings: List[Finding],
                              rule: Optional[Rule] = None
                              ) -> List[Finding]:
    """Cross-reference runtime sanitizer violations against this run's
    static findings. A violation is COVERED when some static finding
    sits at the same file (two-component path tail — the fingerprint
    normalization) and line: the linter already told the user. Anything
    else surfaces as GL013 — the static pass vouched for a site the
    runtime proved dirty."""
    from .baseline import path_tail

    rule = rule or RuntimeCoverageGap()
    covered = {(path_tail(f.path), f.line) for f in findings}
    out: List[Finding] = []
    seen: set = set()
    for v in violations:
        vpath = str(v.get("path") or "")
        vline = int(v.get("line") or 0)
        if not vpath:
            continue  # site-less evidence: nothing to cross-reference
        if (path_tail(vpath), vline) in covered:
            continue
        kind = str(v.get("kind", "violation"))
        key = (path_tail(vpath), vline, kind)
        if key in seen:
            continue  # one finding per site+kind, however many trips
        seen.add(key)
        label = _KIND_LABEL.get(kind, kind)
        detail = str(v.get("detail", ""))[:200]
        func = str(v.get("func", "") or "")
        out.append(Finding(
            rule=rule.code, path=vpath, line=max(1, vline), col=1,
            message=(f"runtime evidence: {label}"
                     + (f" in {func}()" if func else "")
                     + (f" [{detail}]" if detail else "")
                     + " — but the static pass reports no finding at "
                       "this line; rule blind spot or true dynamic "
                       "behavior (if dynamic, audit via "
                       "--write-baseline)"),
            snippet=str(v.get("snippet", ""))[:200]))
    return out
