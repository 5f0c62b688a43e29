"""Test config: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of testing multi-device logic with multiple
CPU contexts (SURVEY.md §4): ``xla_force_host_platform_device_count=8``
gives 8 CPU "chips" so sharding/collective paths compile and execute without
TPU hardware.  The pin is also what makes ``mx.tpu(i)`` mean virtual device
*i* and the Pallas kernels run interpreted (mxnet_tpu.context
``platform_pinned_to_cpu``).  The chip is checked by chip_smoke.py, in its
own process.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cpu_pin import pin_cpu  # noqa: E402

pin_cpu(8)

# This suite runs WITHOUT jax's persistent compilation cache (only the
# chip entry points place one: chipbench.common.place_compile_cache).
# An earlier note here warned that on jax 0.4.37 / XLA:CPU a deserialized
# executable corrupted params under back-to-back donated dispatches
# (Module.run_steps / Trainer.step_k).  Re-tested on the installed jax
# 0.9.0: test_run_steps, test_sync_free, test_module, test_metric,
# test_optimizer and test_fused_dist (134 tests, -m "") pass bit-exactly
# with every executable a cache hit, so that hazard is gone.  The cache
# stays off here because a fresh checkout starts it empty, and because
# every XLA:CPU cache hit logs a machine-feature mismatch error
# (cpu_aot_loader: +prefer-no-scatter) that would bury real output.


@pytest.fixture(scope="module", autouse=True)
def _executed_ops_of_this_module(request):
    """Audit test_operator's ``_COVERED_ELSEWHERE`` as each claimed module
    finishes: the ops it names for that file must have executed in that
    file (tests/test_zz_op_coverage.py ``stale_claims``).  A file is one
    worker's under ``--dist loadfile``, so the audit holds however the
    suite is spread.  ``registry.EXECUTED_OPS`` is this module's alone
    while it runs and the session's again afterwards.  A selection inside
    the file (``-k``, a ``::`` node id) or a failed test is not audited."""
    from mxnet_tpu.ops import registry
    session_wide, failed = registry.EXECUTED_OPS, request.session.testsfailed
    registry.EXECUTED_OPS = here = set()
    yield
    registry.EXECUTED_OPS = session_wide | here
    config = request.config
    if (config.option.keyword or any("::" in a for a in config.args)
            or request.session.testsfailed > failed):
        return
    from tests.test_zz_op_coverage import stale_claims
    relpath = os.path.relpath(str(request.node.path), str(config.rootpath))
    stale = stale_claims(relpath, here)
    assert not stale, (
        "_COVERED_ELSEWHERE (tests/test_operator.py) claims that %s "
        "executes these ops, but it ran none of them: %r" % (relpath, stale))


def _instants(monkeypatch, name):
    """``MXNET_TRACE=1`` around a test, the ring emptied.  Yields
    ``said()``: the ``args`` of every instant called ``name`` since."""
    from mxnet_tpu import tracing
    monkeypatch.setenv("MXNET_TRACE", "1")
    tracing.reconfigure()
    tracing.reset()
    yield lambda: [r["args"] for r in tracing.ring_records()
                   if r["name"] == name]
    monkeypatch.delenv("MXNET_TRACE")
    tracing.reconfigure()


@pytest.fixture
def conv_fold_instants(monkeypatch):
    """``said(nodes_only=False)``: the ``args`` of every
    ``mx.conv.space_to_depth`` instant of the test (ops/nn.py: a
    ``Convolution`` that folded its stride); ``nodes_only`` drops those
    from eager calls and shape inference, which run the op under no node's
    scope."""
    for said in _instants(monkeypatch, "mx.conv.space_to_depth"):
        yield lambda nodes_only=False: [a for a in said()
                                        if a["node"] or not nodes_only]


@pytest.fixture
def loop_lower_instants(monkeypatch):
    """``said()``: the ``args`` of every ``mx.loop.lower`` instant of the
    test (ops/control_flow.py: a loop node lowered to its scan)."""
    yield from _instants(monkeypatch, "mx.loop.lower")


@pytest.fixture
def jaxpr_eqns():
    """``walk(jaxpr)``: every equation of a jaxpr and of the jaxprs its
    equations hold, each as ``(path, eqn)``; ``path`` names the primitives
    that enclose it, outermost first (``("scan", "remat2")``: inside a
    checkpoint's backward inside a scan)."""
    def walk(jaxpr, path=()):
        for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
            yield path, eqn
            for held in eqn.params.values():
                for sub in (held if isinstance(held, (list, tuple))
                            else (held,)):
                    if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                        yield from walk(sub, path + (eqn.primitive.name,))
    return walk
