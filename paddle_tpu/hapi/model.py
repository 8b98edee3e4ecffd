"""High-level ``Model`` API — prepare / fit / evaluate / predict.

Reference parity: python/paddle/hapi/model.py:788 (``Model``; fit :1243,
evaluate :1443, predict :1539) with its Static/DynamicGraphAdapter split.
TPU-native design: there is exactly one adapter — ``prepare`` builds a jitted
functional train/eval step (params + optimizer state as explicit carries,
dropout keys threaded), so the whole step compiles to one XLA program.  That
replaces both reference adapters and is where the MXU actually gets fed.
"""
from __future__ import annotations

import itertools
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd
from ..core import random as _random
from ..io import DataLoader, Dataset
from ..metric import Metric
from ..nn.layer.base import Layer
from ..optimizer.optimizer import Optimizer
from . import callbacks as cb_mod


def _to_tuple(x):
    if x is None:
        return ()
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


class _LazyLogs(dict):
    """Step logs whose values materialize on first read.

    The jitted train step returns unmaterialized ``jax.Array`` scalars;
    forcing them to floats every batch is a device sync that serializes
    dispatch.  Values registered via :meth:`set_lazy` stay as pending thunks
    until a consumer (a callback, verbose logging, epoch summary) actually
    reads them — so ``fit(verbose=0)`` with no reading callbacks keeps the
    dispatch chain fully asynchronous."""

    def __init__(self, **eager):
        super().__init__(**eager)
        self._lazy = {}

    def set_lazy(self, key, thunk):
        super().pop(key, None)
        self._lazy[key] = thunk

    def _force(self, key):
        thunk = self._lazy.pop(key, None)
        if thunk is not None:
            super().__setitem__(key, thunk())

    def materialize(self) -> "_LazyLogs":
        for key in list(self._lazy):
            self._force(key)
        return self

    def __getitem__(self, key):
        self._force(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self._force(key)
        return super().get(key, default)

    def __contains__(self, key):
        return key in self._lazy or super().__contains__(key)

    def __len__(self):
        return super().__len__() + len(self._lazy)

    def __iter__(self):
        self.materialize()
        return super().__iter__()

    def keys(self):
        self.materialize()
        return super().keys()

    def values(self):
        self.materialize()
        return super().values()

    def items(self):
        self.materialize()
        return super().items()

    def copy(self):
        return dict(self.materialize())


def _traced_steps(batches, step_nums):
    """Each batch's turn of the fit loop inside a
    ``jax.profiler.StepTraceAnnotation``: a ``start_device_trace`` capture
    then holds the product's steps on the device trace's clock (the wait
    for the next batch lies between two steps).  Costs nothing measurable
    while no capture runs."""
    for batch in batches:
        with jax.profiler.StepTraceAnnotation("train",
                                              step_num=next(step_nums)):
            yield batch


class Model:
    def __init__(self, network: Layer, inputs=None, labels=None):
        del inputs, labels  # static-graph InputSpec not needed under jit
        self.network = network
        self._optimizer: Optional[Optimizer] = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._train_step = None
        self._eval_step = None
        self._pred_step = None
        self._opt_state = None
        self._fit_params = None  # live jit-path params, mid-epoch
        self.stop_training = False

    # -- setup ---------------------------------------------------------------
    def prepare(self, optimizer: Optional[Optimizer] = None, loss=None,
                metrics: Optional[Sequence[Metric]] = None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = list(metrics) if metrics else []
        self._amp = amp_configs or {}
        self._build_steps()

    def _build_steps(self):
        net = self.network
        loss_fn = self._loss
        opt = self._optimizer
        metrics = self._metrics

        def forward_loss(params, inputs, labels):
            outputs = autograd.functional_call(net, params, _to_tuple(inputs))
            outputs_t = _to_tuple(outputs)
            loss = loss_fn(*outputs_t, *_to_tuple(labels))
            metric_outs = tuple(m.compute(outputs_t[0], labels[0] if isinstance(
                labels, (list, tuple)) else labels) for m in metrics)
            return loss, (outputs_t, metric_outs)

        if opt is not None:
            def train_step(params, opt_state, rng, inputs, labels):
                def inner(p):
                    with _random.rng_scope(rng):
                        return forward_loss(p, inputs, labels)

                (loss, aux), grads = jax.value_and_grad(inner, has_aux=True)(params)
                params, opt_state = opt.update(grads, opt_state, params)
                return params, opt_state, loss, aux[1]

            self._train_step = jax.jit(train_step)

        def eval_step(params, inputs, labels):
            loss, (outputs, metric_outs) = forward_loss(params, inputs, labels)
            return loss, metric_outs

        self._eval_step = jax.jit(eval_step)

        def pred_step(params, inputs):
            return autograd.functional_call(net, params, _to_tuple(inputs))

        self._pred_step = jax.jit(pred_step)

    # -- data plumbing -------------------------------------------------------
    @staticmethod
    def _split_batch(batch):
        """(x, y) convention: last element is the label, rest are inputs
        (matches hapi's inputs/labels split)."""
        if isinstance(batch, (list, tuple)):
            if len(batch) >= 2:
                return tuple(batch[:-1]), batch[-1]
            return (batch[0],), None
        return (batch,), None

    def _loader(self, data, batch_size, shuffle, num_workers, drop_last=False):
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last)
        raise TypeError(f"expected Dataset or DataLoader, got {type(data)}")

    # -- training loop -------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            prefetch_to_device=False):
        """``prefetch_to_device=True`` (or a device) overlaps host→device
        transfer of batch N+1 with compute of batch N via a DeviceFeeder
        thread (io/prefetch.py); step logs materialize lazily, so with
        ``verbose=0`` and no value-reading callbacks the whole epoch
        dispatches asynchronously."""
        assert self._optimizer is not None, "call prepare(optimizer, loss) first"
        from ..core import tape as _tape

        loader = self._loader(train_data, batch_size, shuffle, num_workers,
                              drop_last=drop_last)
        prefetch = prefetch_to_device and not getattr(
            loader, "prefetch_to_device", False)
        params = autograd.parameters_dict(self.network)
        if self._opt_state is None and not _tape.enabled():
            self._opt_state = self._optimizer.init(params)

        # periodic elastic checkpointing rides the callback list when the
        # elastic flags are set (fleet's ElasticConfig sets them)
        from ..core import flags as _flags

        if (int(_flags.get_flag("elastic_save_every")) > 0
                and _flags.get_flag("elastic_ckpt_dir")):
            from ..elastic.checkpoint import ElasticCheckpoint

            callbacks = list(callbacks) if callbacks else []
            if not any(isinstance(c, ElasticCheckpoint) for c in callbacks):
                callbacks.append(ElasticCheckpoint(
                    _flags.get_flag("elastic_ckpt_dir"),
                    save_every=int(_flags.get_flag("elastic_save_every")),
                    keep_last=int(_flags.get_flag("elastic_keep_last"))))
        # the goodput watchdog rides the callback list the same way when
        # the watchdog flag is on; with watchdog_checkpoint_on_anomaly +
        # elastic_ckpt_dir it also gets a checkpoint_fn over the live fit
        # state so a NaN/spiking loss saves a pre-divergence checkpoint
        if _flags.get_flag("watchdog"):
            from ..utils.watchdog import WatchdogCallback

            callbacks = list(callbacks) if callbacks else []
            if not any(isinstance(c, WatchdogCallback) for c in callbacks):
                wcb = WatchdogCallback(
                    heartbeat_dir=os.environ.get("PDTPU_ELASTIC_DIR"))
                ckpt_dir = _flags.get_flag("elastic_ckpt_dir")
                if (_flags.get_flag("watchdog_checkpoint_on_anomaly")
                        and ckpt_dir):
                    from ..elastic.checkpoint import (ElasticCheckpoint,
                                                      save_checkpoint)

                    # reuse ElasticCheckpoint's live-state flattening
                    # (fit's jit path keeps params in _fit_params mid-epoch)
                    saver = ElasticCheckpoint(ckpt_dir, save_every=0)
                    saver.set_model(self)

                    def _anomaly_ckpt(reason, _s=saver, _w=wcb):
                        return save_checkpoint(
                            str(ckpt_dir), _s._flat_state(), _w._gstep,
                            keep_last=int(
                                _flags.get_flag("elastic_keep_last")))

                    wcb.watchdog.checkpoint_fn = _anomaly_ckpt
                callbacks.append(wcb)
        cbs = cb_mod.CallbackList(callbacks, model=self,
                                  params={"epochs": epochs, "verbose": verbose,
                                          "steps": _safe_len(loader),
                                          "batch_size": batch_size,
                                          "log_freq": log_freq})
        cbs.on_train_begin()
        self.stop_training = False
        step_nums = itertools.count()
        for epoch in range(epochs):
            cbs.on_epoch_begin(epoch)
            self.network.train()
            for m in self._metrics:
                m.reset()
            logs = {}
            from ..core import tape as _tape
            batches = loader
            if prefetch:
                from ..io.prefetch import device_prefetch

                batches = device_prefetch(
                    loader, device=None if prefetch_to_device is True
                    else prefetch_to_device)
            for step, batch in enumerate(_traced_steps(batches, step_nums)):
                cbs.on_train_batch_begin(step)
                inputs, labels = self._split_batch(batch)
                if _tape.enabled():
                    loss, metric_outs = self._tape_fit_step(inputs, labels)
                    params = autograd.parameters_dict(self.network)
                else:
                    rng = _random.next_key()
                    params, self._opt_state, loss, metric_outs = \
                        self._train_step(params, self._opt_state, rng, inputs,
                                         labels)
                    # the jit path carries params outside the network until
                    # epoch end; checkpoint callbacks need the live values
                    self._fit_params = params
                # lazy logs: float(loss) is a device sync — defer it until a
                # callback/verbose consumer actually reads the value so the
                # steady-state dispatch chain stays asynchronous
                logs = _LazyLogs(step=step)
                logs.set_lazy("loss", lambda l=loss: float(l))
                for m, mo in zip(self._metrics, metric_outs):
                    val = _metric_update(m, mo)
                    logs.set_lazy(
                        m.name(),
                        lambda v=val: (float(np.asarray(v).ravel()[0])
                                       if v is not None else None))
                cbs.on_train_batch_end(step, logs)
            autograd.load_parameters(self.network, params)
            epoch_logs = {"loss": logs.get("loss")}
            for m in self._metrics:
                epoch_logs[m.name()] = m.accumulate()
            if eval_data is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_data, batch_size=batch_size,
                                          verbose=0, num_workers=num_workers)
                epoch_logs.update({f"eval_{k}": v for k, v in eval_logs.items()})
            cbs.on_epoch_end(epoch, epoch_logs)
            if save_dir and (epoch + 1) % save_freq == 0:
                self.save(f"{save_dir}/epoch_{epoch}")
            if self.stop_training:
                break
        cbs.on_train_end()
        autograd.load_parameters(self.network, params)
        return self

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None):
        loader = self._loader(eval_data, batch_size, False, num_workers)
        self.network.eval()
        for m in self._metrics:
            m.reset()
        params = autograd.parameters_dict(self.network)
        losses = []
        for batch in loader:
            inputs, labels = self._split_batch(batch)
            loss, metric_outs = self._eval_step(params, inputs, labels)
            # defer the scalar sync: batches keep dispatching while earlier
            # losses are still on device
            losses.append(loss)
            for m, mo in zip(self._metrics, metric_outs):
                _metric_update(m, mo)
        logs = {"loss": float(np.mean([np.asarray(l) for l in losses]))
                if losses else 0.0}
        for m in self._metrics:
            logs[m.name()] = m.accumulate()
        self.network.train()
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=True,
                callbacks=None, verbose=1):
        loader = self._loader(test_data, batch_size, False, num_workers)
        self.network.eval()
        params = autograd.parameters_dict(self.network)
        outs = []
        for batch in loader:
            inputs, _ = self._split_batch(batch) if isinstance(batch, (tuple, list)) \
                else ((batch,), None)
            out = self._pred_step(params, inputs)
            # keep batch outputs on device until the loop ends — np.asarray
            # per batch is a sync that serializes dispatch
            outs.append(_to_tuple(out))
        self.network.train()
        n_outputs = len(outs[0]) if outs else 0
        if stack_outputs and outs:
            return [np.concatenate([np.asarray(b[i]) for b in outs], axis=0)
                    for i in range(n_outputs)]
        return [tuple(np.asarray(o) for o in b) for b in outs]

    def train_batch(self, inputs, labels=None):
        from ..core import tape as _tape

        if _tape.enabled():
            return self._train_batch_tape(inputs, labels)
        params = autograd.parameters_dict(self.network)
        if self._opt_state is None:
            self._opt_state = self._optimizer.init(params)
        rng = _random.next_key()
        params, self._opt_state, loss, _ = self._train_step(
            params, self._opt_state, rng, _to_tuple(inputs), labels)
        autograd.load_parameters(self.network, params)
        return float(loss)

    def _train_batch_tape(self, inputs, labels):
        """Eager tape path (ref DynamicGraphAdapter.train_batch,
        hapi/model.py:588: forward → loss.backward() → minimize →
        clear_gradients), used when dygraph.guard() is active."""
        loss, _ = self._tape_fit_step(inputs, labels)
        return float(loss)

    def _tape_fit_step(self, inputs, labels):
        opt = self._optimizer
        if opt._parameters is None:
            opt._parameters = self.network.parameters()
        outputs = _to_tuple(self.network(*_to_tuple(inputs)))
        loss = self._loss(*outputs, *_to_tuple(labels))
        loss.backward()
        opt.minimize(loss)
        self.network.clear_gradients()
        labels0 = labels[0] if isinstance(labels, (list, tuple)) else labels
        metric_outs = tuple(m.compute(outputs[0], labels0)
                            for m in self._metrics)
        return loss, metric_outs

    def eval_batch(self, inputs, labels=None):
        params = autograd.parameters_dict(self.network)
        loss, _ = self._eval_step(params, _to_tuple(inputs), labels)
        return float(loss)

    def predict_batch(self, inputs):
        params = autograd.parameters_dict(self.network)
        return np.asarray(self._pred_step(params, _to_tuple(inputs)))

    # -- persistence ---------------------------------------------------------
    def save(self, path, training=True):
        from ..utils import checkpoint

        checkpoint.save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            # tape-mode fit updates the optimizer's own bound state
            # (optimizer._state); the jit path updates self._opt_state —
            # persist whichever actually trained
            opt_state = self._optimizer._state or self._opt_state
            if opt_state is not None:
                checkpoint.save({"opt": opt_state}, path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..utils import checkpoint

        state = checkpoint.load(path + ".pdparams")
        self.network.set_state_dict(state)
        if not reset_optimizer:
            try:
                opt = checkpoint.load(path + ".pdopt")
                self._opt_state = opt["opt"]
                if self._optimizer is not None and self._optimizer._state:
                    self._optimizer._state = opt["opt"]
            except FileNotFoundError:
                pass

    def parameters(self):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        lines = [repr(self.network)]
        total = sum(int(np.prod(p.shape)) for p in self.network.parameters())
        lines.append(f"Total params: {total:,}")
        s = "\n".join(lines)
        print(s)
        return {"total_params": total}


def _safe_len(loader):
    try:
        return len(loader)
    except TypeError:
        return None


def _metric_update(metric, compute_out):
    """Metrics whose compute() passes (pred, label) through take two update
    args (Precision/Recall/Auc); Accuracy-style metrics take the single
    compute result (ref hapi unpacks compute outputs the same way)."""
    if isinstance(compute_out, tuple):
        return metric.update(*compute_out)
    return metric.update(compute_out)
