"""ImageRecordIter: native-pipeline image-record iterator.

TPU-native equivalent of the reference's C++ ImageRecordIter
(src/io/iter_image_recordio_2.cc, registered in src/io/io.cc:337): sharded
record reads, OMP-parallel JPEG decode+resize in C++
(mxnet_tpu/native/io_native.cc), vectorized augment (mirror/mean/std) in
numpy, and a double-buffered background prefetch thread standing in for
dmlc::ThreadedIter (src/io/iter_prefetcher.h).  Falls back to the PIL
decode path when the native library can't build.
"""
from __future__ import annotations

import logging
import os
import queue
import threading

import numpy as np

from .base import MXNetError, env
from .io import DataBatch, DataDesc, DataIter, _ProducerError
from .ndarray.ndarray import array as nd_array
from . import recordio
from . import native


class ImageRecordIter(DataIter):
    """reference params mirror src/io/image_rec_parser params +
    augmenter params (image_aug_default.cc)."""

    def __init__(self, path_imgrec, data_shape, batch_size,
                 label_width=1, shuffle=False, mean_r=0.0, mean_g=0.0,
                 mean_b=0.0, std_r=1.0, std_g=1.0, std_b=1.0,
                 rand_mirror=False, rand_crop=False, resize=-1,
                 part_index=0, num_parts=1, round_batch=True,
                 preprocess_threads=None, prefetch_buffer=2, seed=0,
                 data_name='data', label_name='softmax_label',
                 device_prefetch=False, device=None, **kwargs):
        super().__init__(batch_size)
        # device_prefetch: keep ONE batch in flight to the device —
        # next() returns the already-transferring batch t and immediately
        # starts batch t+1's async jax.device_put, so the host→device
        # copy overlaps the consumer's compute (the transfer leg of the
        # reference's ThreadedIter overlap; the decode/augment leg is the
        # _producer thread below).  Feeds the multi-step driver
        # (Module.run_steps) without any host work on the hot path.
        self._device_prefetch = device_prefetch
        self._device = device
        self._dev_next = None
        self._dev_err = None
        if not os.path.exists(path_imgrec):
            raise MXNetError(f"record file not found: {path_imgrec}")
        self.path = path_imgrec
        self.data_shape = tuple(data_shape)
        assert len(self.data_shape) == 3, "data_shape must be (C, H, W)"
        self.batch_size = batch_size
        self.label_width = label_width
        self.shuffle = shuffle
        self.rand_mirror = rand_mirror
        self.rand_crop = rand_crop
        self.resize = resize
        self.round_batch = round_batch
        self._rng = np.random.RandomState(seed)
        self.mean = np.array([mean_r, mean_g, mean_b],
                             np.float32).reshape(3, 1, 1)
        self.std = np.array([std_r, std_g, std_b],
                            np.float32).reshape(3, 1, 1)
        self.nthreads = preprocess_threads or \
            env("MXNET_CPU_WORKER_NTHREADS", os.cpu_count() or 4)

        self._native = native.available()
        if self._native:
            offsets = native.index_rec_file(path_imgrec)
        else:
            logging.warning("ImageRecordIter: native IO lib unavailable, "
                            "using PIL fallback (slower)")
            offsets = self._py_index()
        # shard for this worker (reference: dmlc InputSplit partitioning)
        if num_parts > 1:
            n = len(offsets)
            c = n // num_parts
            offsets = offsets[part_index * c:(part_index + 1) * c]
        self._offsets = np.asarray(offsets, dtype=np.int64)
        self._order = np.arange(len(self._offsets))

        self.provide_data = [DataDesc(data_name,
                                      (batch_size,) + self.data_shape)]
        self.provide_label = [DataDesc(
            label_name, (batch_size, label_width) if label_width > 1
            else (batch_size,))]

        self._prefetch_n = prefetch_buffer
        self._queue = None
        self._worker = None
        self._stop = threading.Event()
        self.reset()

    def _py_index(self):
        offsets = []
        r = recordio.MXRecordIO(self.path, 'r')
        while True:
            pos = r.tell()
            if r.read() is None:
                break
            offsets.append(pos)
        r.close()
        return np.asarray(offsets, dtype=np.int64)

    # -- pipeline ----------------------------------------------------------
    def _load_batch(self, idxs):
        offs = self._offsets[idxs]
        if self._native:
            raws = native.read_records(self.path, offs)
        else:
            r = recordio.MXRecordIO(self.path, 'r')
            raws = []
            for o in offs:
                r.seek(int(o))
                raws.append(r.read())
            r.close()
        labels = np.zeros((len(raws), self.label_width), np.float32)
        jpegs = []
        for i, raw in enumerate(raws):
            header, img = recordio.unpack(raw)
            lab = np.atleast_1d(np.asarray(header.label, np.float32))
            labels[i, :min(self.label_width, lab.size)] = \
                lab[:self.label_width]
            jpegs.append(img)
        c, h, w = self.data_shape
        # decode size must cover the crop; with resize set, decode at
        # (>=resize, aspect not preserved — a deliberate simplification of
        # the reference's shorter-edge resize) but never below (h, w)
        dec_h = max(h, self.resize) if self.resize > 0 else h
        dec_w = max(w, self.resize) if self.resize > 0 else w
        if self._native and hasattr(native.get_lib(),
                                    "jpeg_decode_augment_batch"):
            # fused native path: decode+crop+mirror+normalize+NCHW in one
            # OMP pass (io_native.cc jpeg_decode_augment_batch); augmenter
            # randomness drawn here so semantics match the split path
            nimg = len(jpegs)
            # rng is consumed only when a crop actually happens — the same
            # condition as the split path, so seeds stay reproducible
            # across both
            if (dec_h != h or dec_w != w) and self.rand_crop:
                y0 = self._rng.randint(0, dec_h - h + 1, nimg)
                x0 = self._rng.randint(0, dec_w - w + 1, nimg)
            else:
                y0 = np.full(nimg, (dec_h - h) // 2, np.int32)
                x0 = np.full(nimg, (dec_w - w) // 2, np.int32)
            flips = (self._rng.rand(nimg) < 0.5 if self.rand_mirror
                     else np.zeros(nimg, bool))
            arr, fails = native.decode_augment_batch(
                jpegs, dec_h, dec_w, h, w, y0, x0, flips,
                self.mean.ravel()[:c], self.std.ravel()[:c], c,
                self.nthreads)
            if fails:
                logging.debug("%d corrupt images zero-filled", fails)
            labels = labels[:, 0] if self.label_width == 1 else labels
            return arr, labels
        if self._native:
            arr, fails = native.decode_jpeg_batch(
                jpegs, dec_h, dec_w, c, self.nthreads)
            if fails:
                logging.debug("%d corrupt images zero-filled", fails)
        else:
            from .image import imdecode
            outs = []
            for b in jpegs:
                im = np.asarray(imdecode(b, 1 if c == 3 else 0)
                                .asnumpy(), np.uint8)
                from PIL import Image
                im = np.asarray(Image.fromarray(
                    im if c == 3 else im[:, :, 0]).resize(
                        (dec_w, dec_h), Image.BILINEAR), np.uint8)
                if c == 1:
                    im = im[:, :, None]
                outs.append(im)
            arr = np.stack(outs)
        # random / center crop to (h, w) — offsets drawn vectorized, the
        # SAME rng consumption as the fused native path, so a given seed
        # crops identically whether or not the native lib is present
        if arr.shape[1] != h or arr.shape[2] != w:
            H, W = arr.shape[1], arr.shape[2]
            nimg = arr.shape[0]
            if self.rand_crop:
                y0s = self._rng.randint(0, H - h + 1, nimg)
                x0s = self._rng.randint(0, W - w + 1, nimg)
            else:
                y0s = np.full(nimg, (H - h) // 2, np.int64)
                x0s = np.full(nimg, (W - w) // 2, np.int64)
            out = np.empty((nimg, h, w, c), arr.dtype)
            for i in range(nimg):
                out[i] = arr[i, y0s[i]:y0s[i] + h, x0s[i]:x0s[i] + w]
            arr = out
        # NHWC uint8 -> NCHW float32, mirror, normalize (vectorized)
        arr = arr.transpose(0, 3, 1, 2).astype(np.float32)
        if self.rand_mirror:
            flip = self._rng.rand(arr.shape[0]) < 0.5
            arr[flip] = arr[flip, :, :, ::-1]
        if self.mean.any():
            arr -= self.mean
        if (self.std != 1.0).any():
            arr /= self.std
        labels = labels[:, 0] if self.label_width == 1 else labels
        return arr, labels

    def _producer(self, order, out_queue, stop):
        # queue/stop passed by value: a worker outliving reset() keeps
        # talking to ITS epoch's queue, never the replacement's
        try:
            n = len(order)
            for start in range(0, n - self.batch_size + 1,
                               self.batch_size):
                if stop.is_set():
                    return
                idxs = order[start:start + self.batch_size]
                out_queue.put(self._load_batch(idxs))
            rem = n % self.batch_size
            if rem and self.round_batch:
                # wrap around to fill the final batch (reference:
                # round_batch pads from the epoch start); datasets smaller
                # than batch_size tile cyclically
                idxs = np.concatenate([order[n - rem:],
                                       order[np.arange(
                                           self.batch_size - rem) % n]])
                batch = self._load_batch(idxs)
                out_queue.put(batch + (self.batch_size - rem,))
        except BaseException as e:  # noqa: BLE001 — crossing a thread
            # surface the failure on the CONSUMER side: without this, a
            # corrupt/mis-shaped record would look like a (possibly empty)
            # end of epoch — silent truncation, and a permanent hang for
            # any caller double-buffering off this iterator
            out_queue.put(_ProducerError(e))
        finally:
            out_queue.put(None)

    def reset(self):
        self._stop.set()
        if self._worker is not None:
            # drain so the producer can observe stop and exit
            try:
                while self._queue.get_nowait() is not None:
                    pass
            except queue.Empty:
                pass
            self._worker.join(timeout=5)
            if self._worker.is_alive():
                # a wedged producer can't corrupt the NEW epoch (it holds
                # the old queue/stop objects), but it is a leaked thread
                # pinning file handles — say so instead of masking it
                logging.warning(
                    "ImageRecordIter.reset: previous prefetch worker did "
                    "not stop within 5s (stuck in native decode/IO?); "
                    "leaking the daemon thread")
        self._stop = threading.Event()
        self._done = False
        self._dev_next = None   # drop any in-flight device batch
        self._dev_err = None    # ...and any parked prefetch failure
        order = self._order.copy()
        if self.shuffle:
            self._rng.shuffle(order)
        self._queue = queue.Queue(maxsize=self._prefetch_n)
        self._worker = threading.Thread(
            target=self._producer, args=(order, self._queue, self._stop),
            daemon=True)
        self._worker.start()

    def next_raw(self):
        """Next batch as HOST numpy arrays (data, label, pad) — no NDArray
        wrap, no device transfer.  For callers that manage placement
        themselves (ONE uint8 device_put per batch; wrapping through
        next() would eagerly device_put and cost extra host<->device
        crossings on a remote-attached chip)."""
        if self._done:
            raise StopIteration
        while True:
            try:
                item = self._queue.get(timeout=1.0)
                break
            except queue.Empty:
                # the producer posts a sentinel even on failure (its
                # finally clause) — an empty queue with a DEAD worker
                # means the thread was killed outright; hanging here
                # forever would silently wedge training
                if self._worker is not None and not self._worker.is_alive():
                    self._done = True
                    raise MXNetError(
                        "ImageRecordIter: prefetch worker died without "
                        "reporting a result — cannot continue the epoch")
        if item is None:
            self._done = True
            raise StopIteration
        if isinstance(item, _ProducerError):
            self._done = True
            raise MXNetError(
                "ImageRecordIter pipeline failed in the prefetch thread: "
                "%r" % (item.exc,)) from item.exc
        if len(item) == 3:
            data, label, pad = item
        else:
            data, label = item
            pad = 0
        return data, label, pad

    def _device_batch(self):
        """Next batch with its async device transfer already started."""
        import jax
        data, label, pad = self.next_raw()
        from .ndarray import NDArray
        return DataBatch(
            [NDArray(jax.device_put(data, self._device))],
            [NDArray(jax.device_put(label, self._device))], pad=pad,
            provide_data=self.provide_data,
            provide_label=self.provide_label)

    def next(self):
        if self._device_prefetch:
            if self._dev_err is not None:
                err, self._dev_err = self._dev_err, None
                raise err
            cur = self._dev_next
            if cur is None:
                cur = self._device_batch()   # first call of the epoch
            try:
                # start batch t+1's transfer before handing out batch t:
                # the copy overlaps the consumer's compute
                self._dev_next = self._device_batch()
            except StopIteration:
                self._dev_next = None
            except Exception as e:  # noqa: BLE001 — t+1's pipeline died,
                # but batch t in hand is GOOD: deliver it, raise on the
                # NEXT call (dropping cur would silently consume a batch
                # from the record stream without ever training on it)
                self._dev_next = None
                self._dev_err = e
            return cur
        data, label, pad = self.next_raw()
        return DataBatch([nd_array(data)], [nd_array(label)], pad=pad,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)


class ImageRecordUInt8Iter(ImageRecordIter):
    """Raw pre-decoded uint8 records: no JPEG decode at training time.

    Reference: ImageRecordUInt8Iter (src/io/io.cc:337-758) — the input-
    pipeline fast path when the host CPU cannot decode fast enough to feed
    the accelerator.  Records carry fixed-shape HWC uint8 payloads (pack
    with ``tools/im2rec.py --pack-raw S``); iteration is pure byte movement
    (crop + mirror + NCHW in native code, io_native.cc crop_flip_u8_batch).
    Output batches are uint8 NCHW — normalization belongs ON DEVICE, where
    it fuses into the training step (e.g. ResNet's bn_data input
    BatchNorm); mean/std parameters are therefore rejected here, exactly
    like the reference's uint8 iterator which ignores them.
    """

    def __init__(self, path_imgrec, data_shape, batch_size,
                 stored_shape=None, output_layout="NCHW", **kwargs):
        identity = {"mean_r": 0.0, "mean_g": 0.0, "mean_b": 0.0,
                    "std_r": 1.0, "std_g": 1.0, "std_b": 1.0}
        for k, ident in identity.items():
            v = kwargs.pop(k, None)
            if v is not None and float(v) != ident:
                raise MXNetError(
                    "ImageRecordUInt8Iter outputs raw uint8; apply "
                    "mean/std on device (it fuses into the step)")
        if output_layout not in ("NCHW", "NHWC"):
            raise MXNetError(
                f"output_layout must be NCHW or NHWC, got {output_layout}")
        # NHWC is the host FAST path: an unflipped row is one memcpy
        # (~10x the NCHW gather on one core) and the HWC->CHW transpose
        # moves to the device where it fuses into the uint8->bf16 cast
        self._output_layout = output_layout
        self._stored_shape = tuple(stored_shape) if stored_shape else None
        super().__init__(path_imgrec, data_shape, batch_size, **kwargs)
        if output_layout == "NHWC":
            c, h, w = self.data_shape
            self.provide_data = [DataDesc(self.provide_data[0].name,
                                          (batch_size, h, w, c),
                                          dtype=np.uint8, layout="NHWC")]

    def _infer_stored_shape(self, payload_len):
        c = self.data_shape[0]
        if payload_len % c:
            raise MXNetError(
                f"raw record payload {payload_len} not divisible by "
                f"channels {c}")
        side = int(round((payload_len // c) ** 0.5))
        if side * side * c != payload_len:
            raise MXNetError(
                f"raw record payload {payload_len} is not square; pass "
                f"stored_shape=(H, W)")
        return (side, side)

    def _load_batch(self, idxs):
        offs = self._offsets[idxs]
        if self._native:
            raws = native.read_records(self.path, offs)
        else:
            r = recordio.MXRecordIO(self.path, 'r')
            raws = []
            for o in offs:
                r.seek(int(o))
                raws.append(r.read())
            r.close()
        labels = np.zeros((len(raws), self.label_width), np.float32)
        payloads = []
        for i, raw in enumerate(raws):
            header, img = recordio.unpack(raw)
            lab = np.atleast_1d(np.asarray(header.label, np.float32))
            labels[i, :min(self.label_width, lab.size)] = \
                lab[:self.label_width]
            payloads.append(img)
        c, h, w = self.data_shape
        if self._stored_shape is None:
            self._stored_shape = self._infer_stored_shape(len(payloads[0]))
        dh, dw = self._stored_shape
        nimg = len(payloads)
        if (dh != h or dw != w) and self.rand_crop:
            y0 = self._rng.randint(0, dh - h + 1, nimg)
            x0 = self._rng.randint(0, dw - w + 1, nimg)
        else:
            y0 = np.full(nimg, (dh - h) // 2, np.int32)
            x0 = np.full(nimg, (dw - w) // 2, np.int32)
        flips = (self._rng.rand(nimg) < 0.5 if self.rand_mirror
                 else np.zeros(nimg, bool))
        nhwc = self._output_layout == "NHWC"
        # feature-test the EXACT symbol: a stale prebuilt .so may carry
        # crop_flip_u8_batch but not the newer nhwc variant
        want_sym = "crop_flip_u8_nhwc_batch" if nhwc \
            else "crop_flip_u8_batch"
        if self._native and hasattr(native.get_lib(), want_sym):
            fn = native.crop_flip_u8_nhwc_batch if nhwc \
                else native.crop_flip_u8_batch
            arr = fn(payloads, dh, dw, h, w, y0, x0, flips, c,
                     self.nthreads)
        else:  # pure-numpy fallback, same semantics
            arr = np.empty((nimg, h, w, c) if nhwc else (nimg, c, h, w),
                           np.uint8)
            for i, p in enumerate(payloads):
                im = np.asarray(p, dtype=np.uint8).reshape(dh, dw, c) \
                    if isinstance(p, np.ndarray) \
                    else np.frombuffer(p, np.uint8).reshape(dh, dw, c)
                crop = im[y0[i]:y0[i] + h, x0[i]:x0[i] + w]
                if flips[i]:
                    crop = crop[:, ::-1]
                arr[i] = crop if nhwc else crop.transpose(2, 0, 1)
        labels = labels[:, 0] if self.label_width == 1 else labels
        return arr, labels
