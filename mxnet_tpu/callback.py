"""Training callbacks (reference: python/mxnet/callback.py).

SYNC CONTRACT (the sync-free training loop):
metric accumulation in fit/score is device-resident, and a callback that
reads the metric — ``get_name_value()`` → ``EvalMetric.sync()`` — is the
ONLY point where the host blocks on a device readback.  Callbacks that
observe metrics therefore set the loop's sync cadence: Speedometer
syncs once per ``frequent`` batches, LogValidationMetricsCallback once
per evaluation, and a loop with no metric-reading callback syncs once
per epoch (the epoch-end log).  tests/test_sync_free.py asserts this.
"""
from __future__ import annotations

import logging
import math
import time


def module_checkpoint(mod, prefix, period=1, save_optimizer_states=False):
    """Epoch-end checkpoint callback (reference: callback.py:27)."""
    period = int(max(1, period))

    def _callback(iter_no, sym=None, arg=None, aux=None):
        if (iter_no + 1) % period == 0:
            mod.save_checkpoint(prefix, iter_no + 1, save_optimizer_states)
    return _callback


def do_checkpoint(prefix, period=1, sharded_async=False):
    """reference: callback.py:55 — save symbol+params every `period` epochs.

    ``sharded_async=True`` saves through checkpoint.AsyncCheckpointer
    (sharded format, per-epoch prefixes): the epoch boundary only pays a
    device-side snapshot and training continues while the shards write in
    the background.  The returned callback carries the checkpointer as
    ``_callback.checkpointer`` — call ``.wait()`` after fit() before
    reading the final checkpoint."""
    from .model import save_checkpoint
    period = int(max(1, period))
    if sharded_async:
        from .checkpoint import AsyncCheckpointer
        ck = AsyncCheckpointer()

        def _callback(iter_no, sym, arg, aux):
            if (iter_no + 1) % period == 0:
                ck.save_checkpoint(prefix, iter_no + 1, sym, arg, aux)
        _callback.checkpointer = ck
        return _callback

    def _callback(iter_no, sym, arg, aux):
        if (iter_no + 1) % period == 0:
            save_checkpoint(prefix, iter_no + 1, sym, arg, aux)
    return _callback


def log_train_metric(period, auto_reset=False):
    """reference: callback.py log_train_metric."""
    def _callback(param):
        if param.nbatch % period == 0 and param.eval_metric is not None:
            name_value = param.eval_metric.get_name_value()
            for name, value in name_value:
                logging.info('Iter[%d] Batch[%d] Train-%s=%f',
                             param.epoch, param.nbatch, name, value)
            if auto_reset:
                param.eval_metric.reset()
    return _callback


class Speedometer:
    """Throughput logging (reference: callback.py:120 Speedometer).

    Reading the metric here (every ``frequent`` batches) triggers the
    lazy ``EvalMetric.sync()`` — with device-resident metrics this is
    the training loop's ONLY per-interval host sync, so ``frequent`` is
    literally the host-readbacks-per-epoch dial: N batches at
    ``frequent=F`` cost floor((N-1)/F) syncs here (count hits
    ``% F == 0`` on batch indices 1..N-1) plus the epoch-end log's one,
    not N."""

    def __init__(self, batch_size, frequent=50, auto_reset=True):
        self.batch_size = batch_size
        self.frequent = frequent
        self.init = False
        self.tic = 0
        self.last_count = 0
        self.auto_reset = auto_reset

    def __call__(self, param):
        count = param.nbatch
        if self.last_count > count:
            self.init = False
        self.last_count = count

        if self.init:
            if count % self.frequent == 0:
                speed = self.frequent * self.batch_size / \
                    (time.time() - self.tic)
                if param.eval_metric is not None:
                    name_value = param.eval_metric.get_name_value()
                    if self.auto_reset:
                        param.eval_metric.reset()
                    msg = 'Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec'
                    msg += '\t%s=%f' * len(name_value)
                    logging.info(msg, param.epoch, count, speed,
                                 *sum(name_value, ()))
                else:
                    logging.info("Iter[%d] Batch [%d]\tSpeed: %.2f samples/sec",
                                 param.epoch, count, speed)
                self.tic = time.time()
        else:
            self.init = True
            self.tic = time.time()


class ProgressBar:
    """reference: callback.py ProgressBar."""

    def __init__(self, total, length=80):
        self.bar_len = length
        self.total = total

    def __call__(self, param):
        count = param.nbatch
        filled_len = int(round(self.bar_len * count / float(self.total)))
        percents = math.ceil(100.0 * count / float(self.total))
        prog_bar = '=' * filled_len + '-' * (self.bar_len - filled_len)
        logging.info('[%s] %s%s\r', prog_bar, percents, '%')


class LogValidationMetricsCallback:
    """reference: callback.py LogValidationMetricsCallback.

    ``get_name_value()`` below is the lazy sync point: the whole
    validation pass accumulates on device and this callback's read is
    its one host readback."""

    def __call__(self, param):
        if not param.eval_metric:
            return
        name_value = param.eval_metric.get_name_value()
        for name, value in name_value:
            logging.info('Epoch[%d] Validation-%s=%f', param.epoch, name,
                         value)
