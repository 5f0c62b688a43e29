"""Audit of the op coverage that is claimed, not shown (VERDICT r3 item 7).

``tests/test_operator.py``'s registry gate accepts ``_COVERED_ELSEWHERE``
— a declarative map op -> dedicated test file — on faith.  The claims are
checked against the record of what ran: when a claimed module finishes,
tests/conftest.py hands ``stale_claims`` the ops that module executed
(``registry.EXECUTED_OPS``), and an op claimed for a file that no longer
executes it fails the suite there.  The check belongs to the claimed
file, not to the end of the session, because under the driver's
``-n 6 --dist loadfile`` no process sees the session: a worker sees the
files it was given.  The tests here are of the audit itself.
"""
import os


def stale_claims(relpath, executed):
    """Ops that ``_COVERED_ELSEWHERE`` claims for test file ``relpath``
    and that are not in ``executed``.  Alias-aware (same rule as
    test_operator's gate): executing any alias of an OpDef counts for
    all of them."""
    from mxnet_tpu.ops import registry
    from tests.test_operator import _COVERED_ELSEWHERE, _EXEMPT
    executed = set(executed)
    alias_groups = {}
    for n in registry.list_ops():
        alias_groups.setdefault(id(registry.get(n)), []).append(n)
    for aliases in alias_groups.values():
        if any(a in executed for a in aliases):
            executed.update(aliases)
    return sorted(op for op, f in _COVERED_ELSEWHERE.items()
                  if f == relpath and op not in executed
                  and op not in _EXEMPT)


def test_covered_elsewhere_claims_executed():
    det = "tests/test_detection.py"
    claimed = stale_claims(det, ())
    assert {"ROIPooling", "MultiBoxPrior", "_contrib_MultiBoxPrior"} \
        <= set(claimed)
    assert stale_claims(det, claimed) == []
    assert stale_claims(det, set(claimed) - {"ROIPooling"}) == ["ROIPooling"]
    # one alias executed covers the OpDef's other names
    assert "MultiBoxPrior" not in stale_claims(
        det, {"_contrib_MultiBoxPrior"})
    # ops that ran in another file do not count for this one's claims
    assert stale_claims(det, {"RNN", "Dropout"}) == claimed
    # a file with no claim has nothing to go stale
    assert stale_claims("tests/test_module.py", ()) == []


def test_a_loop_node_and_the_ops_of_its_body_count_as_executed():
    """``_foreach`` is claimed for tests/test_control_flow.py; the ops of
    a loop's body run under the body's own interpreter, inside a scan, and
    are recorded there like any node's."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym
    from mxnet_tpu.ops import registry
    loops = "tests/test_control_flow.py"
    assert stale_claims(loops, ()) == ["_foreach"]
    seen, registry.EXECUTED_OPS = registry.EXECUTED_OPS, set()
    try:
        _, last = sym.contrib.foreach(
            lambda _, h: (None, sym.arctanh(h * 0.5)), None,
            sym.Variable("data"), num_iter=2, name="loop")
        last.eval(data=mx.nd.ones((2,)))
        ran = set(registry.EXECUTED_OPS)
    finally:
        registry.EXECUTED_OPS = seen | registry.EXECUTED_OPS
    assert {"_foreach", "arctanh", "_mul_scalar"} <= ran
    assert stale_claims(loops, ran) == []


def test_claimed_files_exist(request):
    from tests.test_operator import _COVERED_ELSEWHERE
    root = str(request.config.rootpath)
    missing = sorted({f for f in set(_COVERED_ELSEWHERE.values())
                      if not os.path.exists(os.path.join(root, f))})
    assert not missing, (
        "_COVERED_ELSEWHERE names test files that do not exist: %r"
        % missing)
