"""Generic jitted train step over a dygraph Layer + paddle_tpu Optimizer.

This is the TPU answer to the reference's static-graph training executor
(InterpreterCore running forward+backward+optimizer ops,
ref: /root/reference/paddle/fluid/framework/new_executor/interpretercore.cc):
one compiled XLA program per step — forward, loss, backward
(jax.value_and_grad), and the optimizer's fused multi-tensor update — with
parameter/optimizer-state buffers donated, honoring whatever NamedShardings
the parameters carry (TP/ZeRO placements from fleet)."""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..framework import autograd, random as _random
from ..framework.tensor import Tensor
from . import mesh as mesh_mod


class TrainStep:
    def __init__(self, layer, optimizer, loss_fn: Optional[Callable] = None,
                 batch_spec: Optional[list] = None, donate: bool = True,
                 remat: bool = False, grad_accum_steps: int = 1,
                 grad_accum_avg: bool = True):
        self.layer = layer
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.batch_spec = batch_spec
        self.donate = donate
        self.remat = remat
        # gradient merge (ref: fleet/meta_optimizers/gradient_merge_
        # optimizer.py): accumulate k micro-steps device-side, apply the
        # optimizer update once per k
        self.grad_accum_steps = max(1, int(grad_accum_steps))
        self.grad_accum_avg = bool(grad_accum_avg)
        self._acc = None
        self._opt_steps = 0
        self._params = [p for _, p in layer.named_parameters()
                        if not p.stop_gradient]
        self._param_arrays = [p.data for p in self._params]
        self._states = [optimizer._get_state(p) for p in self._params]
        self._metas = [
            (float(p.optimize_attr.get("learning_rate", 1.0)),
             optimizer._wd_for_param(p), False) for p in self._params]
        self._stepno = 0
        self._compiled = None

    def _make_forward_loss(self):
        layer = self.layer
        params = self._params
        loss_fn = self.loss_fn

        def forward_loss(param_arrays, batch_arrays, key):
            saved = [p._data for p in params]
            for p, a in zip(params, param_arrays):
                p._data = a
            try:
                ts = [Tensor(a, stop_gradient=True) for a in batch_arrays]
                with autograd.no_grad(), _random.key_scope(key):
                    if loss_fn is not None:
                        out = loss_fn(layer, *ts)
                    else:
                        out = layer(*ts)
                    if isinstance(out, (tuple, list)):
                        out = out[0]
                loss = out.data if isinstance(out, Tensor) else out
            finally:
                for p, a in zip(params, saved):
                    p._data = a
            return loss

        return forward_loss

    def _build(self, batch_shapes):
        opt = self.optimizer
        fused = opt._make_fused(self._metas)
        forward_loss = self._make_forward_loss()

        def step(param_arrays, states, batch_arrays, lr, stepno, key):
            loss, grads = jax.value_and_grad(forward_loss)(
                param_arrays, batch_arrays, key)
            new_p, new_s = fused(param_arrays, grads, states, lr, stepno)
            return loss, new_p, new_s

        donate = (0, 1) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    def _build_accum(self):
        """Gradient-merge pair: an accumulate-only micro-step and an
        apply-update step run every `grad_accum_steps` calls."""
        opt = self.optimizer
        fused = opt._make_fused(self._metas)
        forward_loss = self._make_forward_loss()
        k = self.grad_accum_steps
        avg = self.grad_accum_avg

        def accum(param_arrays, batch_arrays, acc, key):
            loss, grads = jax.value_and_grad(forward_loss)(
                param_arrays, batch_arrays, key)
            return loss, [a + g for a, g in zip(acc, grads)]

        def apply(param_arrays, states, acc, lr, stepno):
            gs = [a / k for a in acc] if avg else acc
            new_p, new_s = fused(param_arrays, gs, states, lr, stepno)
            return new_p, new_s, [jnp.zeros_like(a) for a in acc]

        # donate the accumulator in accum (pure elementwise program) and
        # params only in apply — optimizer states are passed undonated,
        # as in static/executor.py (ROADMAP S5 re-tests that)
        return (jax.jit(accum, donate_argnums=(2,) if self.donate else ()),
                jax.jit(apply, donate_argnums=(0,) if self.donate else ()))

    def __call__(self, *batch):
        batch_arrays = [b.data if isinstance(b, Tensor) else jnp.asarray(b)
                        for b in batch]
        if self.batch_spec:
            batch_arrays = [
                mesh_mod.shard_tensor_data(a, s) if s is not None else a
                for a, s in zip(batch_arrays, self.batch_spec)]
        key = _random.next_key()
        if self.grad_accum_steps > 1:
            if self._compiled is None:
                self._compiled = self._build_accum()
                self._acc = [jnp.zeros_like(a) for a in self._param_arrays]
            accum_fn, apply_fn = self._compiled
            self._stepno += 1
            loss, self._acc = accum_fn(self._param_arrays, batch_arrays,
                                       self._acc, key)
            if self._stepno % self.grad_accum_steps == 0:
                self._opt_steps += 1
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                stepno = jnp.asarray(self._opt_steps, jnp.float32)
                self._param_arrays, self._states, self._acc = apply_fn(
                    self._param_arrays, self._states, self._acc, lr,
                    stepno)
            return Tensor(loss)
        if self._compiled is None:
            self._compiled = self._build(tuple(a.shape for a in batch_arrays))
        self._stepno += 1
        self._opt_steps = self._stepno
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        stepno = jnp.asarray(self._stepno, jnp.float32)
        loss, self._param_arrays, self._states = self._compiled(
            self._param_arrays, self._states, batch_arrays, lr, stepno, key)
        return Tensor(loss)

    def sync_to_layer(self):
        """Write the (donated) training buffers back into the Layer/optimizer
        for checkpointing or eager eval."""
        for p, a in zip(self._params, self._param_arrays):
            p._data = a
        for p, st in zip(self._params, self._states):
            self.optimizer._accumulators[p.name] = st
        self.optimizer._step_count = self._opt_steps
