"""env-knob: every MXNET_* getenv is declared, live, and documented.

``base.declare_env`` is the machine-readable knob registry
(:mod:`mxnet_tpu.analysis.knobs` is its analysis-facing view).  Knob
rot has two directions and this rule closes both:

* **undeclared read** — a ``MXNET_*`` name consulted via
  ``base.env`` / ``os.environ.get`` / ``os.getenv`` / subscript that
  was never ``declare_env``-ed: invisible to ``list_env_flags()``, to
  the generated ROBUSTNESS.md knob table, and to anyone tuning a job.
* **stale declaration** (package mode only) — a registered knob no
  code reads: documentation describing behavior that no longer exists.

Package mode also checks the docs themselves: every registered knob
must appear in docs/ROBUSTNESS.md (regenerate the folded table with
``python -m mxnet_tpu.analysis --knob-table``).
"""
from __future__ import annotations

import ast

from ..lint import Finding

_ENV_OBJS = {"environ"}


def _is_env_func(name: str) -> bool:
    """Call names that perform an env lookup: ``env``/``getenv`` and
    local aliases like ``_env`` / ``_base_env`` — but never
    ``declare_env``, which is the registration itself."""
    if name == "declare_env":
        return False
    return name in ("env", "getenv") or name.endswith("_env")


def _mxnet_literal(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and node.value.startswith("MXNET_"):
        return node.value
    return None


def _read_site(node):
    """Knob name if ``node`` is an env-lookup call/subscript."""
    if isinstance(node, ast.Call):
        f = node.func
        name = None
        if isinstance(f, ast.Name) and _is_env_func(f.id):
            name = True
        elif isinstance(f, ast.Attribute):
            if f.attr in ("get", "pop", "setdefault") \
                    and _is_environ(f.value):
                name = True
            elif _is_env_func(f.attr):
                # module-qualified reads: base.env(...), os.getenv(...)
                name = True
        if name and node.args:
            return _mxnet_literal(node.args[0])
    elif isinstance(node, ast.Subscript) and _is_environ(node.value):
        sl = node.slice
        return _mxnet_literal(sl)
    return None


def _is_environ(node):
    if isinstance(node, ast.Name) and node.id in _ENV_OBJS:
        return True
    if isinstance(node, ast.Attribute) and node.attr in _ENV_OBJS:
        return True
    return False


def _registry():
    from ..knobs import registry
    return registry()


class _EnvKnobRule:
    name = "env-knob"

    def check_file(self, ctx, project):
        reads = project.scratch.setdefault("env-knob-reads", set())
        declared = _registry()
        for node in ast.walk(ctx.tree):
            # declare_env("MXNET_X", ...) is the registration itself
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "declare_env":
                continue
            knob = _read_site(node)
            if knob is None:
                continue
            reads.add(knob)
            if knob not in declared:
                yield Finding(
                    rule=self.name, path=ctx.relpath, line=node.lineno,
                    message="env knob %s is read here but never "
                    "declared via base.declare_env — invisible to "
                    "list_env_flags(), the ROBUSTNESS.md knob table "
                    "and the --knob-table export; declare it with a "
                    "type, default and doc string" % knob)

    def finalize(self, project):
        if not project.is_package:
            return
        from ..knobs import docs_missing, registry
        reads = project.scratch.get("env-knob-reads", set())
        base_ctx = next((c for c in project.files
                         if c.relpath == "base.py"), None)

        def _decl_line(knob):
            if base_ctx is not None:
                for ln, text in enumerate(base_ctx.lines, start=1):
                    if '"%s"' % knob in text:
                        return ln
            return 1

        reg = registry()
        for knob in sorted(set(reg) - reads):
            yield Finding(
                rule=self.name, path="base.py", line=_decl_line(knob),
                message="env knob %s is declared in the registry but "
                "no code reads it — stale documentation; wire it up "
                "or delete the declaration" % knob)
        for knob, entry in sorted(reg.items()):
            if not entry.doc:
                yield Finding(
                    rule=self.name, path="base.py",
                    line=_decl_line(knob),
                    message="env knob %s is declared with an EMPTY doc "
                    "string — the generated ROBUSTNESS.md table would "
                    "ship a blank 'what it does' row; say what it "
                    "does" % knob)
        missing, docs_path = docs_missing(project.root)
        for knob in missing:
            yield Finding(
                rule=self.name, path=str(docs_path), line=1,
                message="env knob %s is registered but absent from the "
                "ROBUSTNESS.md knob table; regenerate it with "
                "`python -m mxnet_tpu.analysis --knob-table`" % knob)


RULE = _EnvKnobRule()
