"""async-safety checker family.

The messenger plane mixes asyncio event loops with real threads (the
BatchingQueue dispatcher, a store's commit thread, native calls), which
is exactly where review keeps catching the same defects:

- ``blocking-call``: a synchronous blocker (``time.sleep``, subprocess,
  a blocking ``threading.Lock.acquire``) inside an ``async def`` stalls
  the WHOLE event loop — every connection, heartbeat and timer on it;
- ``lock-across-await``: a ``with <thread-lock>:`` block containing an
  ``await`` parks the lock across a suspension point, so any OTHER task
  or thread contending for it deadlocks the loop (asyncio locks use
  ``async with``; thread locks must be released before awaiting);
- ``cross-loop-call``: calling ``loop.call_soon``/``create_task`` on a
  STORED loop from sync code may run on a foreign thread — the home-loop
  idiom is ``call_soon_threadsafe`` (this checker keeps it that way);
- ``await-in-section``: a ``with tracing.section(...)`` block (or a
  ``@tracing.sectioned`` coroutine) containing an ``await`` — a section
  is self time of SYNCHRONOUS work on one thread's stack
  (common/tracing.py); across a suspension it would time the whole
  loop's other work and corrupt the stack the loop meter resets per step.

Heuristic exemptions (calibrated on the shipped tree):

- ``asyncio.get_running_loop().create_task(...)`` and locals assigned
  from an expression containing ``get_running_loop`` are loop-correct by
  construction (``get_running_loop`` raises off-loop, it cannot cross);
- calls wrapped in an argument to ``call_soon_threadsafe`` /
  ``run_coroutine_threadsafe`` ARE the idiom, not a violation;
- ``await x.acquire()`` is an asyncio acquire; only the non-awaited,
  argument-less form is flagged.
"""

from __future__ import annotations

import ast
import re
from typing import List, Tuple

from ceph_tpu.tools.lint.findings import Finding

# sync calls that block the calling thread (and with it, the loop)
_BLOCKING = {
    "time.sleep": "blocks the event loop; use `await asyncio.sleep`",
    "os.system": "blocks the event loop; use an executor",
    "subprocess.run": "blocks the event loop; use "
                      "`asyncio.create_subprocess_exec` or an executor",
    "subprocess.call": "blocks the event loop",
    "subprocess.check_call": "blocks the event loop",
    "subprocess.check_output": "blocks the event loop",
    "socket.create_connection": "blocks the event loop; use "
                                "`asyncio.open_connection`",
}

_LOOP_METHODS = {"call_soon", "call_later", "call_at", "create_task"}
_THREADSAFE = {"call_soon_threadsafe", "run_coroutine_threadsafe"}

_LOCKISH = re.compile(r"(^|[^a-z])(lock|mutex)")
# common/tracing.py's self-time sections: synchronous work only
_SECTION = re.compile(r"^(tracing\.)?section\(")
_SECTIONED = re.compile(r"^(tracing\.)?sectioned\(")


def _lockish(src: str) -> bool:
    # word-start match: `self._lock`, `lock`, `shard_lock` hit;
    # `block`, `self.blocked`, `unlock` (the 'l' follows a letter) miss
    return _LOCKISH.search(src.lower()) is not None


class _Scanner(ast.NodeVisitor):
    def __init__(self, relpath: str, findings: List[Finding]):
        self.relpath = relpath
        self.findings = findings
        # stack of (is_async, get_running_loop_locals, node)
        self.funcs: List[Tuple[bool, set, ast.AST]] = []
        self.threadsafe_depth = 0
        self.await_depth = 0

    # -- function scopes -----------------------------------------------------

    def _visit_func(self, node, is_async: bool) -> None:
        loop_locals = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                    and isinstance(sub.targets[0], ast.Name) \
                    and "get_running_loop" in ast.unparse(sub.value):
                loop_locals.add(sub.targets[0].id)
        self.funcs.append((is_async, loop_locals, node))
        self.generic_visit(node)
        self.funcs.pop()

    def visit_FunctionDef(self, node):
        self._visit_func(node, False)

    def visit_AsyncFunctionDef(self, node):
        for deco in node.decorator_list:
            src = ast.unparse(deco)
            if _SECTIONED.match(src):
                self.findings.append(Finding(
                    check="async-safety/await-in-section",
                    file=self.relpath, line=node.lineno,
                    key=f"{src}@L{node.lineno}",
                    message=f"`@{src}` on `async def {node.name}`: it "
                            f"would time the creation of the coroutine, "
                            f"not its steps — put sections around the "
                            f"synchronous stretches inside"))
        self._visit_func(node, True)

    def visit_Lambda(self, node):
        # a lambda inherits its enclosing context (it runs wherever it is
        # called; for the threadsafe-wrap exemption the wrap matters)
        self.generic_visit(node)

    @property
    def in_async(self) -> bool:
        return bool(self.funcs) and self.funcs[-1][0]

    # -- await tracking (awaited calls are not blocking) ---------------------

    def visit_Await(self, node):
        self.await_depth += 1
        self.generic_visit(node)
        self.await_depth -= 1

    # -- calls ---------------------------------------------------------------

    def visit_Call(self, node):
        func = node.func
        dotted = ""
        if isinstance(func, (ast.Attribute, ast.Name)):
            try:
                dotted = ast.unparse(func)
            except Exception:  # pragma: no cover - unparse is total here
                dotted = ""

        if self.in_async:
            self._check_blocking(node, func, dotted)

        if isinstance(func, ast.Attribute) and func.attr in _THREADSAFE:
            self.threadsafe_depth += 1
            self.generic_visit(node)
            self.threadsafe_depth -= 1
            return

        if isinstance(func, ast.Attribute) and func.attr in _LOOP_METHODS:
            self._check_cross_loop(node, func)

        self.generic_visit(node)

    def _check_blocking(self, node, func, dotted: str) -> None:
        for pat, why in _BLOCKING.items():
            if dotted == pat or dotted.endswith("." + pat):
                self.findings.append(Finding(
                    check="async-safety/blocking-call", file=self.relpath,
                    line=node.lineno, key=f"{pat}@L{node.lineno}",
                    message=f"`{pat}` inside `async def` "
                            f"{self._func_name()}: {why}"))
                return
        # blocking .acquire() on a lock-looking receiver, not awaited:
        # a threading lock acquire would park the whole loop
        if (isinstance(func, ast.Attribute) and func.attr == "acquire"
                and not node.args and not node.keywords
                and self.await_depth == 0
                and _lockish(ast.unparse(func.value))):
            self.findings.append(Finding(
                check="async-safety/blocking-call", file=self.relpath,
                line=node.lineno,
                key=f"acquire:{ast.unparse(func.value)}@L{node.lineno}",
                message=f"non-awaited blocking "
                        f"`{ast.unparse(func.value)}.acquire()` inside "
                        f"`async def` {self._func_name()}: a thread-lock "
                        f"acquire stalls the event loop (await an asyncio "
                        f"lock, or release before suspension)"))

    def _check_cross_loop(self, node, func: ast.Attribute) -> None:
        if self.threadsafe_depth:
            return  # wrapped in call_soon_threadsafe(...): the idiom
        if self.in_async:
            return  # on-loop by definition (async bodies run in the loop)
        recv = ast.unparse(func.value)
        if recv.startswith("asyncio"):
            return  # asyncio.get_running_loop()/asyncio.ensure_future
        if self.funcs and isinstance(func.value, ast.Name) \
                and func.value.id in self.funcs[-1][1]:
            return  # local assigned from get_running_loop: on-loop
        self.findings.append(Finding(
            check="async-safety/cross-loop-call", file=self.relpath,
            line=node.lineno, key=f"{recv}.{func.attr}@L{node.lineno}",
            message=f"`{recv}.{func.attr}(...)` from sync code in "
                    f"{self._func_name()}: a stored loop may be homed on "
                    f"another thread — use "
                    f"`{recv}.call_soon_threadsafe(...)` or prove the "
                    f"caller is on that loop via "
                    f"`asyncio.get_running_loop()`"))

    # -- with blocks ---------------------------------------------------------

    def visit_With(self, node):
        has_await = any(isinstance(x, (ast.Await, ast.AsyncFor,
                                       ast.AsyncWith))
                        for x in ast.walk(node))
        if has_await:
            for item in node.items:
                src = ast.unparse(item.context_expr)
                if _SECTION.match(src):
                    self.findings.append(Finding(
                        check="async-safety/await-in-section",
                        file=self.relpath, line=node.lineno,
                        key=f"{src}@L{node.lineno}",
                        message=f"`{src}` holds an `await` in "
                                f"{self._func_name()}: a section times "
                                f"synchronous work — across a suspension "
                                f"it would measure everybody else's steps "
                                f"and break the per-step stack (end it "
                                f"before the await, open another after)"))
                if _lockish(src):
                    self.findings.append(Finding(
                        check="async-safety/lock-across-await",
                        file=self.relpath, line=node.lineno,
                        key=f"{src}@L{node.lineno}",
                        message=f"thread lock `{src}` held across an "
                                f"`await` in {self._func_name()}: any "
                                f"other thread or task contending for it "
                                f"deadlocks against the suspended task "
                                f"(narrow the critical section, or use "
                                f"`async with` on an asyncio lock)"))
        self.generic_visit(node)

    def _func_name(self) -> str:
        for is_async, _, node in reversed(self.funcs):
            if hasattr(node, "name"):
                return f"`{node.name}`"
        return "<module>"


def check(sources: List[Tuple[str, str]]) -> List[Finding]:
    findings: List[Finding] = []
    for relpath, text in sources:
        try:
            tree = ast.parse(text)
        except SyntaxError:
            continue  # codec family reports unparsable files
        _Scanner(relpath, findings).visit(tree)
    return findings
