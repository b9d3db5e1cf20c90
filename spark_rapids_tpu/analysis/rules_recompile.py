"""R001: recompile hazards.

Round-5 VERDICT: q4 spent 3.5 h inside XLA compiles because programs were
re-traced per (scale, query). Flare's core argument — compilation cost must
be amortized, never paid per call — is enforced here in its statically
checkable forms:

- ``jax.jit`` / ``pjit`` / ``jax.shard_map`` constructed inside a for/while
  loop or comprehension: a fresh closure per iteration defeats jit's
  function-identity cache, so every iteration re-traces and may recompile.
- a jit construction invoked immediately (``jax.jit(f)(x)``): the wrapped
  function is dropped after one call, so its compile is paid every time the
  enclosing code runs.
- ``static_argnums`` / ``static_argnames`` passed an unhashable container
  literal built from non-literal elements — flagged conservatively only when
  the value is a dict/set literal (always wrong: jax needs a hashable spec).

The engine's sanctioned pattern is a keyed program cache around the jit
construction (``_cached_jit`` in execs/tpu_execs.py, ``_PROGRAMS`` in
shuffle/partition_kernel.py); anything jit-like created per call should
route through one.
"""
from __future__ import annotations

import ast
from typing import List

from spark_rapids_tpu.analysis.core import (Finding, Rule, SourceFile,
                                            call_name, register)

#: callables that construct a compiled program when invoked
_JIT_NAMES = {"jax.jit", "jit", "named_jit", "pjit", "jax.pjit",
              "jax.shard_map", "shard_map"}


def is_jit_call(node: ast.Call) -> bool:
    name = call_name(node)
    if name in _JIT_NAMES:
        return True
    # functools.partial(jax.jit, ...) builds the same hazard lazily
    if name in ("functools.partial", "partial") and node.args:
        inner = node.args[0]
        if isinstance(inner, (ast.Attribute, ast.Name)):
            from spark_rapids_tpu.analysis.core import dotted_name
            return dotted_name(inner) in _JIT_NAMES
    return False


def _in_cache_guard(src: SourceFile, node: ast.Call) -> bool:
    """True when the jit construction sits inside the sanctioned keyed-cache
    idiom: an ``if`` branch that also stores into a subscripted container
    (``_PROGRAMS[key] = fn`` after ``fn = _PROGRAMS.get(key)``) — one
    compile per key, however often the enclosing loop runs."""
    for anc in src.ancestors(node):
        if isinstance(anc, (ast.For, ast.AsyncFor, ast.While,
                            ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
        if isinstance(anc, ast.If):
            for stmt in ast.walk(anc):
                if isinstance(stmt, ast.Assign) and any(
                        isinstance(t, ast.Subscript) for t in stmt.targets):
                    return True
    return False


@register
class RecompileHazards(Rule):
    rule_id = "R001"
    title = "recompile hazards (per-call jit construction)"

    def check(self, src: SourceFile) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call) or not is_jit_call(node):
                continue
            name = call_name(node) or "jit"
            if src.inside_loop(node) and not _in_cache_guard(src, node):
                findings.append(src.finding(
                    self.rule_id, node,
                    f"{name}(...) constructed inside a loop: each iteration "
                    f"builds a fresh closure, defeating jit's program cache "
                    f"and re-tracing per iteration; hoist it out or route it "
                    f"through a keyed program cache (_cached_jit pattern)"))
            parent = src.parent(node)
            if isinstance(parent, ast.Call) and parent.func is node:
                findings.append(src.finding(
                    self.rule_id, node,
                    f"{name}(fn)(...) invoked immediately: the compiled "
                    f"program is dropped after one call, so tracing and "
                    f"compilation are paid on every execution; bind the "
                    f"jitted function once and reuse it"))
            for kw in node.keywords:
                if kw.arg in ("static_argnums", "static_argnames") and \
                        isinstance(kw.value, (ast.Dict, ast.Set)):
                    findings.append(src.finding(
                        self.rule_id, kw.value,
                        f"{name}: {kw.arg} given an unhashable "
                        f"dict/set literal; use an int/str tuple"))
        return findings
