"""Pin JAX to a virtual multi-device CPU backend.

One shared implementation of the backend pin every CPU-side entry point
uses (tests, CI, dist worker scripts, the multichip dryrun).  Why it exists:

* A chip belongs to one process at a time; a unit-test or dryrun process
  must not claim it for work designed for virtual devices.
* ``xla_force_host_platform_device_count=N`` gives N CPU "chips" so
  sharding/collective paths compile and execute without TPU hardware —
  the reference's multiple-CPU-contexts test strategy (SURVEY.md §4).

The pin is also what lets ``mx.tpu(i)`` stand for virtual device *i* and
the Pallas kernels run interpreted (mxnet_tpu.context
``platform_pinned_to_cpu``).  A process started with ``JAX_PLATFORMS=cpu``
is pinned already — JAX honours the variable itself — and needs this
module only for the device count.

Call :func:`pin_cpu` BEFORE any jax computation runs (import-time is fine:
XLA_FLAGS is read at backend *initialization*, which happens on first
device use, not at ``import jax``).
"""
import os


def pin_cpu(n_devices=8, clear_backends=False):
    """Force the CPU platform with ``n_devices`` virtual devices.

    Returns the ``jax`` module for convenience.  ``clear_backends=True``
    additionally tears down any already-initialized backend (needed when a
    process may have touched devices before pinning, e.g. the driver
    calling ``dryrun_multichip`` after other jax work).

    ``n_devices=None`` leaves XLA_FLAGS untouched (one device per process —
    what the multi-process dist worker scripts want, where each process is
    its own "host" in the cluster).
    """
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + " --xla_force_host_platform_device_count=%d" % n_devices
            ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    if clear_backends:
        jax.extend.backend.clear_backends()
    return jax
