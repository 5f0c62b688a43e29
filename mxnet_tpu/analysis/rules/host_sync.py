"""host-sync: host readbacks in hot-path modules must be deliberate.

The sync-free training loop holds because
every device->host readback in the hot path is one of a handful of
counted, contract-bearing sites: ``NDArray.asnumpy``/``wait_to_read``
record themselves, ``EvalMetric.sync`` and
``module.base_module.chunked_device_get`` record their own tags, and
callbacks are documented as the loop's only sync points.  A new
``.asnumpy()`` / ``jax.device_get`` / ``np.asarray(nd)`` /
``float(nd)`` call site in a hot-path module silently re-grows a
per-batch sync — exactly the regression class the sync-count CI gate
exists for, caught here at the SOURCE line instead of as a count drift.

A site passes when its innermost enclosing function itself calls
``profiler.record_host_sync`` (it IS a counted contract site) or when
it carries an ``# analysis: allow(host-sync): <reason>`` annotation
(typically: the value is already host data, or the site runs once per
epoch/process, not per batch).
"""
from __future__ import annotations

import ast

from ..lint import Finding

# Hot-path modules: package-relative path prefixes (ISSUE 5 list).
_HOT_PREFIXES = ("module/", "gluon/trainer.py", "metric.py",
                 "executor.py", "model.py")

_NUMPY_NAMES = {"numpy"}
_JAX_NAMES = {"jax"}


def _is_hot(ctx) -> bool:
    rel = ctx.relpath.replace("\\", "/")
    return rel.startswith(_HOT_PREFIXES) or ctx.hot_marker


def _import_aliases(tree):
    """module-name -> set of local aliases, for numpy and jax."""
    numpy_alias, jax_alias = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    numpy_alias.add(a.asname or a.name)
                elif a.name == "jax":
                    jax_alias.add(a.asname or a.name)
    return numpy_alias or set(_NUMPY_NAMES), jax_alias or set(_JAX_NAMES)


def _records_host_sync(func_node) -> bool:
    """True when ``func_node``'s OWN body calls record_host_sync —
    nested function defs are not descended into: a closure recording a
    sync does not make its enclosing function a contract site."""
    stack = [func_node]
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not func_node:
            continue
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and \
                    f.attr == "record_host_sync":
                return True
            if isinstance(f, ast.Name) and f.id == "record_host_sync":
                return True
        stack.extend(ast.iter_child_nodes(node))
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, numpy_alias, jax_alias):
        self.numpy_alias = numpy_alias
        self.jax_alias = jax_alias
        self.func_stack = []
        self.hits = []   # (line, message)

    def _in_contract_site(self):
        # INNERMOST function only: one recorded sync must not whitelist
        # every other readback in an enclosing function's whole tree
        return bool(self.func_stack) and \
            _records_host_sync(self.func_stack[-1])

    def visit_FunctionDef(self, node):
        self.func_stack.append(node)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        f = node.func
        hit = None
        if isinstance(f, ast.Attribute):
            if f.attr in ("asnumpy", "wait_to_read"):
                hit = ".%s() is a host-blocking device readback" % f.attr
            elif f.attr == "device_get" and isinstance(f.value, ast.Name) \
                    and f.value.id in self.jax_alias:
                hit = "jax.device_get is a host-blocking device readback"
            elif f.attr == "asarray" and isinstance(f.value, ast.Name) \
                    and f.value.id in self.numpy_alias:
                hit = ("np.asarray forces a device->host copy when its "
                       "argument lives on device")
        elif isinstance(f, ast.Name) and f.id == "float" and node.args \
                and isinstance(node.args[0], ast.Name):
            hit = ("float(x) on a device value is a hidden host sync")
        if hit is not None and not self._in_contract_site():
            self.hits.append((node.lineno, hit))
        self.generic_visit(node)


class _HostSyncRule:
    name = "host-sync"

    def check_file(self, ctx, project):
        if not _is_hot(ctx):
            return
        numpy_alias, jax_alias = _import_aliases(ctx.tree)
        v = _Visitor(numpy_alias, jax_alias)
        v.visit(ctx.tree)
        for line, msg in v.hits:
            yield Finding(
                rule=self.name, path=ctx.relpath, line=line,
                message=msg + " in a hot-path module; route it through "
                "a profiler.record_host_sync contract site (metric.sync"
                ", chunked_device_get, ...) or annotate why it is not a "
                "per-batch sync")


RULE = _HostSyncRule()
