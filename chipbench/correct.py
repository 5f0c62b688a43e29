"""The comparison that decides ``correct`` (ISSUE 24, fixed there).

A family's plain reference is run twice on the Module's initial parameters
and resident batch 0: ``ref32`` with everything in float32, ``ref16`` at
the precision the configuration states.  How far ``ref16`` lands from
``ref32`` is what that precision alone does to a quantity, and the Module
is allowed a small multiple of it.  There is no exact-equality condition
and no tensor is excluded by name."""
import math

# the rule's constants; PERF.md records any change, old, new and why
LOSS_REL = 5e-3          # mean loss, Module against ref32
OUT_FACTOR = 4.0         # outputs: rel L2 <= max(OUT_FACTOR * c_out, OUT_FLOOR)
OUT_FLOOR = 0.01
C_DECIDABLE = 0.25       # a tensor whose own c(t) is above this decides nothing
DELTA_FACTOR = 4.0       # deltas: e(t) <= max(DELTA_FACTOR * c(t), DELTA_FLOOR)
DELTA_FLOOR = 0.05
MAX_FAIL_SHARE = 0.02    # of the decided tensors
MIN_DECIDED_SHARE = 1.0 / 3.0


def pair_stats(a, ref):
    """Per-leaf float32 sums for two dicts of arrays with the same keys:
    ``|a - ref|^2``, ``|ref|^2``, ``|a|^2`` and ``<a, ref>``, as one
    jitted call; returns {name: (d2, r2, a2, dot)} of Python floats."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def sums(a, ref):
        out = {}
        for k in ref:
            x = a[k].astype(jnp.float32)
            r = ref[k].astype(jnp.float32)
            out[k] = jnp.stack([jnp.sum(jnp.square(x - r)),
                                jnp.sum(jnp.square(r)),
                                jnp.sum(jnp.square(x)),
                                jnp.sum(x * r)])
        return out

    got = jax.device_get(sums({k: a[k] for k in ref}, ref))
    return {k: tuple(float(x) for x in np.asarray(v)) for k, v in got.items()}


def rel_l2(stat):
    d2, r2, _, _ = stat
    if r2 == 0.0:
        return 0.0 if d2 == 0.0 else math.inf
    return math.sqrt(d2 / r2)


def cosine(stat):
    _, r2, a2, dot = stat
    if r2 == 0.0 or a2 == 0.0:
        return 1.0 if r2 == a2 else 0.0
    return dot / math.sqrt(r2 * a2)


def judge_forward(loss_module, loss32, e_out, c_out):
    """Rule 1: the mean loss and the outputs, Module against ref32."""
    loss_rel = abs(loss_module - loss32) / abs(loss32)
    out_tol = max(OUT_FACTOR * c_out, OUT_FLOOR)
    ok = (math.isfinite(loss_module) and loss_rel <= LOSS_REL
          and e_out <= out_tol)
    return ok, {"loss_module": loss_module, "loss_ref32": loss32,
                "loss_rel": loss_rel, "loss_tol": LOSS_REL,
                "out_rel_l2": e_out, "out_ref16_rel_l2": c_out,
                "out_tol": out_tol}


def judge_deltas(table, output_weight):
    """Rule 2.  ``table`` is {tensor: {"e":, "c":, ...}} over every updated
    tensor; ``output_weight`` names the output layer's weight.  A tensor
    with ``c <= C_DECIDABLE`` is decided at the stated precision.  One
    above it is decided by ``e32`` where the row has it: the Module's same
    step at float32 compute against ``ref32``, where the reference's own
    ``c`` is 0 and the floor alone is the tolerance.  Without ``e32`` it
    is undecidable and decides nothing."""
    own = {t for t, r in table.items() if r["c"] <= C_DECIDABLE}
    in32 = {t for t, r in table.items() if t not in own and "e32" in r}
    undecidable = sorted(t for t in table if t not in own | in32)
    failing = sorted(
        [t for t in own if not table[t]["e"] <= max(
            DELTA_FACTOR * table[t]["c"], DELTA_FLOOR)]
        + [t for t in in32 if not table[t]["e32"] <= DELTA_FLOOR])
    decided = len(own) + len(in32)
    out_ok = output_weight in own | in32 and output_weight not in failing
    share = decided / len(table) if table else 0.0
    ok = (out_ok and share >= MIN_DECIDED_SHARE
          and len(failing) <= MAX_FAIL_SHARE * decided)
    return ok, {"tensors": len(table), "decidable": len(own),
                "decided_in_float32": sorted(in32), "decided_share": share,
                "undecidable": undecidable, "failing": failing,
                "output_weight": output_weight, "output_weight_ok": out_ok}


def judge_window(losses, compiles, attempted, completed):
    """Rule 3: what the measured window itself has to show.  The details
    name the first step whose loss is not finite (its index into the
    window's losses, None where all are finite): a recipe that blows up
    does so at a step count, and a window that holds fewer steps never
    sees it (chipbench/README.md, "A cell's recipe")."""
    nonfinite = next((i for i, x in enumerate(losses)
                      if not math.isfinite(x)), None)
    finite = nonfinite is None
    n = min(10, len(losses) // 2)
    first = sum(losses[:n]) / n if n else math.nan
    last = sum(losses[-n:]) / n if n else math.nan
    ok = (finite and compiles == 0 and completed == attempted
          and n > 0 and last < first)
    return ok, {"losses_finite": finite, "first_nonfinite_step": nonfinite,
                "compiles_in_window": compiles,
                "attempted": attempted, "completed": completed,
                "loss_first_mean": first, "loss_last_mean": last,
                "averaged_over": n}
