"""AS — async-safety. The serving tiers (modkit/, modules/, gateway/) run on
one asyncio event loop; a blocked loop stalls every in-flight request, and a
fire-and-forget task swallows its exception at GC time. These hazards live
*inside* ``async def`` bodies, which the old grep tier could not see.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable

from ..engine import FileContext, Finding, Rule, Scope, dotted_name, register

SERVING_TIERS = frozenset({"modkit", "modules", "gateway", "apps", ""})

#: dotted call names that block the calling thread. ``open`` is deliberately
#: NOT here: config/startup reads from async hooks are idiomatic and small;
#: sustained file streaming goes through executors anyway.
_BLOCKING_CALLS = {
    "time.sleep",
    "sqlite3.connect",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output", "subprocess.Popen",
    "requests.get", "requests.post", "requests.put", "requests.patch",
    "requests.delete", "requests.head", "requests.request",
    "requests.Session",
    "urllib.request.urlopen",
    "socket.create_connection",
}

_SPAWN_CALLS = {"asyncio.ensure_future", "asyncio.create_task",
                "ensure_future", "create_task"}


def _is_spawn_call(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    if name in _SPAWN_CALLS:
        return True
    # loop.create_task(...) — but NOT tg.create_task(...) (TaskGroup retains
    # the task and propagates its exception; that is the recommended safe
    # pattern) and not unrelated domain APIs sharing the method name
    if isinstance(node.func, ast.Attribute) and node.func.attr == "create_task":
        holder = dotted_name(node.func.value).rsplit(".", 1)[-1].lower()
        return "loop" in holder
    return False


@register
class AS01(Rule):
    id = "AS01"
    family = "AS"
    severity = "error"
    description = ("blocking call on the serving path: inside async def, or "
                   "time.sleep anywhere in a serving tier")
    node_types = (ast.Call,)
    tiers = SERVING_TIERS

    def visit(self, node: ast.Call, scope: Scope,
              ctx: FileContext) -> Iterable[Finding]:
        name = dotted_name(node.func)
        if name not in _BLOCKING_CALLS:
            return
        if scope.in_async:
            yield self.finding(
                node, f"blocking call {name}() inside async def "
                f"{getattr(scope.current_function, 'name', '?')} stalls the "
                "event loop — await the async equivalent or push it to an "
                "executor")
        elif name == "time.sleep":
            # even in sync code, sleeping a serving-tier thread is suspect:
            # most sync helpers here are called from the loop. Sanctioned
            # engine-thread retry loops carry a waiver.
            yield self.finding(
                node, "time.sleep() in a serving tier — if this runs on the "
                "event loop it stalls every request; waive only for "
                "dedicated sync threads")


@register
class AS02(Rule):
    id = "AS02"
    family = "AS"
    severity = "error"
    description = ("fire-and-forget task: ensure_future/create_task result "
                   "neither retained nor given a done-callback")
    node_types = (ast.Expr, ast.Assign)

    def visit(self, node: ast.AST, scope: Scope,
              ctx: FileContext) -> Iterable[Finding]:
        if isinstance(node, ast.Expr):
            value = node.value
            discarded = True
        else:  # Assign — only `_ = ...` is still a discard
            value = node.value
            targets = node.targets
            discarded = all(isinstance(t, ast.Name) and t.id == "_"
                            for t in targets)
        if not discarded or not isinstance(value, ast.Call):
            return
        if _is_spawn_call(value):
            yield self.finding(
                value, "fire-and-forget task: the loop holds only a weak "
                "reference, and an exception in it is silently dropped at GC "
                "time — retain the task and attach a done-callback that logs "
                "failures (see modkit.logging_host.observe_task)")


#: host<-device sync entry points: each blocks the scheduler thread until the
#: device drains, serializing host and device work (the pipelining the
#: overlapped decode loop exists to avoid). NON-blocking transfer starts
#: (``.copy_to_host_async()``) are deliberately NOT here: the deep-lookahead
#: sync discipline is "start transfers anywhere in the hot loop, block only
#: at the single sanctioned drain".
_DEVICE_SYNC_CALLS = {"np.asarray", "numpy.asarray", "jax.device_get"}

#: decode-hot-loop method names of a scheduler-thread class (one that defines
#: ``_run_loop``): the steady-state path that runs once per decode chunk.
#: Admission/preemption helpers (rare, inherently synchronizing) are excluded.
_HOT_LOOP_RE = re.compile(
    r"^(_loop_body|_loop_pass|_decode_round\w*|_emit_\w+|_dispatch_\w+|_commit_\w+"
    r"|_read_chunk)$")

#: the sanctioned sync carries this marker in a trailing comment — exactly one
#: deliberate readback per decode round, named at the call site
_SYNC_POINT_MARKER = "sync-point:"


@register
class AS04(Rule):
    id = "AS04"
    family = "AS"
    severity = "error"
    description = ("host-blocking device sync (np.asarray / jax.device_get / "
                   ".block_until_ready) inside a scheduler decode-loop method "
                   "outside the one sanctioned `# sync-point:` drain — and at "
                   "most ONE such drain per hot-loop method (non-blocking "
                   ".copy_to_host_async() transfer starts are always allowed)")
    node_types = (ast.Call,)
    tiers = frozenset({"runtime"})

    def _in_hot_loop(self, scope: Scope) -> bool:
        cls = scope.current_class
        if cls is None:
            return False
        has_run_loop = any(
            isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name == "_run_loop" for n in cls.body)
        if not has_run_loop:
            return False
        return any(
            isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
            and _HOT_LOOP_RE.match(f.name) for f in scope.func_stack)

    #: textual fingerprints of a sanctioned-drain LINE: the marker scan only
    #: counts lines that also contain a device-sync call, so a docstring or
    #: comment merely MENTIONING "sync-point:" cannot fake an earlier drain
    _SYNC_CALL_TOKENS = ("np.asarray", "numpy.asarray", "jax.device_get",
                         "block_until_ready")

    @classmethod
    def _earlier_sync_point(cls, node: ast.Call, scope: Scope,
                            ctx: FileContext) -> bool:
        """True when the enclosing function already sanctioned a sync on an
        EARLIER line — the deep-lookahead discipline is one drain per round
        method (start transfers anywhere, block once)."""
        func = scope.func_stack[-1] if scope.func_stack else None
        if func is None:
            return False
        start = func.lineno
        end = getattr(func, "end_lineno", None) or node.lineno
        for ln in range(start, min(end, node.lineno - 1) + 1):
            if ln == node.lineno:
                break
            if ln > len(ctx.lines):
                continue
            line = ctx.lines[ln - 1]
            if _SYNC_POINT_MARKER in line and any(
                    tok in line for tok in cls._SYNC_CALL_TOKENS):
                return True
        return False

    def visit(self, node: ast.Call, scope: Scope,
              ctx: FileContext) -> Iterable[Finding]:
        name = dotted_name(node.func)
        is_sync = name in _DEVICE_SYNC_CALLS or (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "block_until_ready")
        if not is_sync or not self._in_hot_loop(scope):
            return
        line_text = ctx.lines[node.lineno - 1] if node.lineno <= len(ctx.lines) else ""
        if _SYNC_POINT_MARKER in line_text:
            if self._earlier_sync_point(node, scope, ctx):
                yield self.finding(
                    node, "second `# sync-point:` drain in one hot-loop "
                    "method: the deep-lookahead discipline is ONE blocking "
                    "drain per round — start non-blocking transfers "
                    "(.copy_to_host_async()) for the rest and drain the "
                    "oldest at the single sanctioned point")
            return  # the one sanctioned drain of the decode round
        yield self.finding(
            node, f"host-blocking device sync {name or node.func.attr}() in "
            "a scheduler hot-loop method: it stalls the host until the device "
            "drains, breaking decode/emit overlap — route the value through "
            "the round's single `# sync-point:` drain (non-blocking "
            ".copy_to_host_async() starts are fine anywhere), or waive with "
            "the reason the extra sync is unavoidable")


@register
class AS03(Rule):
    id = "AS03"
    family = "AS"
    severity = "error"
    description = "await while holding a sync (threading) lock"
    node_types = (ast.Await,)

    def visit(self, node: ast.Await, scope: Scope,
              ctx: FileContext) -> Iterable[Finding]:
        if scope.lock_stack:
            lock = scope.lock_stack[-1]
            held = ", ".join(
                dotted_name(item.context_expr) or
                dotted_name(getattr(item.context_expr, "func", item.context_expr))
                for item in lock.items) or "lock"
            yield self.finding(
                node, f"await while holding sync lock ({held}): the lock "
                "stays held across the suspension, so any other coroutine "
                "or thread contending for it deadlocks the loop — release "
                "before awaiting, or use asyncio.Lock")
