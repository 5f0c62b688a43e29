"""Shared training-script plumbing (reference:
example/image-classification/common/fit.py).

Arg parsing + kvstore creation + lr schedule + checkpoint callbacks +
Module.fit — the reference's `fit.fit(args, network, data_loader)` shape.
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..'))

import mxnet_tpu as mx


def add_fit_args(parser):
    """reference: common/fit.py add_fit_args."""
    parser.add_argument('--network', type=str, default=None)
    parser.add_argument('--num-epochs', type=int, default=10)
    parser.add_argument('--batch-size', type=int, default=128)
    parser.add_argument('--lr', type=float, default=0.05)
    parser.add_argument('--lr-factor', type=float, default=0.1)
    parser.add_argument('--lr-step-epochs', type=str, default='')
    parser.add_argument('--optimizer', type=str, default='sgd')
    parser.add_argument('--mom', type=float, default=0.9)
    parser.add_argument('--wd', type=float, default=1e-4)
    parser.add_argument('--kv-store', type=str, default='device')
    parser.add_argument('--dtype', type=str, default='float32',
                        help="compute dtype: float32 | bfloat16 | float16")
    parser.add_argument('--model-prefix', type=str, default=None)
    parser.add_argument('--load-epoch', type=int, default=None)
    parser.add_argument('--disp-batches', type=int, default=20)
    parser.add_argument('--num-examples', type=int, default=60000)
    return parser


def fit(args, network, train, val=None, **kwargs):
    """reference: common/fit.py fit — the universal training entry."""
    logging.basicConfig(level=logging.INFO)
    kv = mx.kv.create(args.kv_store)

    lr_sched = None
    if args.lr_step_epochs:
        epoch_size = max(args.num_examples // args.batch_size
                         // max(kv.num_workers, 1), 1)
        steps = [epoch_size * int(e)
                 for e in args.lr_step_epochs.split(',') if e]
        if steps:
            lr_sched = mx.lr_scheduler.MultiFactorScheduler(
                step=steps, factor=args.lr_factor)

    compute_dtype = None
    if args.dtype in ('bfloat16', 'float16'):
        import jax.numpy as jnp
        compute_dtype = jnp.dtype(args.dtype)

    mod = mx.mod.Module(network, context=mx.tpu(0),
                        compute_dtype=compute_dtype,
                        **{k: v for k, v in kwargs.items()
                           if k in ('data_names', 'label_names', 'mesh',
                                    'sharding_rules')})
    arg_params = aux_params = None
    begin_epoch = 0
    if args.model_prefix and args.load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)
        begin_epoch = args.load_epoch

    cbs = [mx.callback.Speedometer(args.batch_size, args.disp_batches)]
    epoch_cbs = []
    if args.model_prefix:
        epoch_cbs.append(mx.callback.do_checkpoint(args.model_prefix))

    opt_params = {'learning_rate': args.lr, 'wd': args.wd}
    if args.optimizer in ('sgd', 'nag', 'signum'):
        opt_params['momentum'] = args.mom
    if lr_sched is not None:
        opt_params['lr_scheduler'] = lr_sched

    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            begin_epoch=begin_epoch,
            arg_params=arg_params, aux_params=aux_params,
            kvstore=kv, optimizer=args.optimizer,
            optimizer_params=opt_params,
            initializer=mx.initializer.Xavier(rnd_type='gaussian',
                                              factor_type='in',
                                              magnitude=2),
            batch_end_callback=cbs, epoch_end_callback=epoch_cbs,
            eval_metric='acc')
    return mod
