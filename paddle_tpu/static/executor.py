"""Static-graph Executor.

ref: /root/reference/python/paddle/fluid/executor.py:1275 Executor.run →
_ExecutorCache (:722,889,634) → StandaloneExecutor/InterpreterCore. Here the
cached artifact is a jitted function evaluating the whole program DAG —
forward, optimizer update (grads via jax.grad over parameter leaves), and
state updates — in one XLA program, with donated buffers for params/states.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.symbolic import (Program, SymbolicTensor,
                                  default_main_program,
                                  default_startup_program)
from ..framework.tensor import Parameter, Tensor


def _collect_graph(targets: List[SymbolicTensor]):
    """Topological node order + leaf tensors reachable from targets."""
    nodes = []
    seen_nodes = set()
    leaf_tensors: Dict[int, Tensor] = {}
    feeds: Dict[str, SymbolicTensor] = {}

    def visit_sym(s: SymbolicTensor):
        if s._node is None:
            if s._feed_name is not None:
                feeds[s._feed_name] = s
            return
        visit_node(s._node)

    def visit_node(n):
        if n.id in seen_nodes:
            return
        seen_nodes.add(n.id)
        for a in n.args:
            if isinstance(a, SymbolicTensor):
                visit_sym(a)
            elif isinstance(a, Tensor):
                leaf_tensors[id(a)] = a
        nodes.append(n)

    for t in targets:
        visit_sym(t)
    return nodes, leaf_tensors, feeds


def _eval_graph(nodes, targets, env):
    """env: {('feed', name): arr, ('t', id): arr}. Returns list of arrays."""
    values: Dict[Tuple[int, int], Any] = {}

    def lookup(a):
        if isinstance(a, SymbolicTensor):
            if a._node is None:
                return env[("feed", a._feed_name)]
            return values[(a._node.id, a._out_idx)]
        if isinstance(a, Tensor):
            return env[("t", id(a))]
        return a

    for n in nodes:
        args = [lookup(a) for a in n.args]
        out = n.impl(*args, **n.kwargs)
        if n.n_outs == 1 and not isinstance(out, (tuple, list)):
            values[(n.id, 0)] = out
        else:
            for i, o in enumerate(out):
                values[(n.id, i)] = o
    return [lookup(t) for t in targets]


_DONATE_OVERRIDE = None


class Executor:
    def __init__(self, place=None):
        self.place = place
        self._cache: Dict[Any, Any] = {}
        self._lr_cache: Dict[Any, Any] = {}

    def run(self, program=None, feed=None, fetch_list=None,
            return_numpy=True, **kwargs):
        feed = feed or {}
        if program is None:
            program = default_main_program()
        if program is default_startup_program() or (
                isinstance(program, Program) and not program._nodes
                and not fetch_list):
            return []
        fetch_list = list(fetch_list or [])
        fetch_syms = [f for f in fetch_list]

        # all graph targets: fetches + state updates + optimizer losses
        state_targets = [s for _, s in program._state_updates]
        opt_losses = [l for _, l in program._optimize_ops]
        all_targets = [t for t in fetch_syms
                       if isinstance(t, SymbolicTensor)] + state_targets \
            + opt_losses
        nodes, leaf_tensors, feeds_map = _collect_graph(all_targets)

        leaf_ids = sorted(leaf_tensors.keys())
        leaf_objs = [leaf_tensors[i] for i in leaf_ids]
        trainable = [t for t in leaf_objs
                     if isinstance(t, Parameter) and not t.stop_gradient]

        # optimizer states (created eagerly, passed as runtime inputs)
        opt_blobs = []
        for opt, loss_sym in program._optimize_ops:
            params = trainable
            states = [opt._get_state(p) for p in params]
            masters = [opt._master_weights.get(p.name) for p in params]
            metas = tuple(
                (float(p.optimize_attr.get("learning_rate", 1.0)),
                 opt._wd_for_param(p), m is not None)
                for p, m in zip(params, masters))
            opt_blobs.append((opt, loss_sym, params, states, metas))

        sig = (id(program), len(program._nodes),
               tuple(sorted(feeds_map.keys())),
               tuple(tuple(v.shape) if hasattr(v, "shape")
                     else np.asarray(v).shape for v in feed.values()),
               tuple(id(t) if isinstance(t, SymbolicTensor) else None
                     for t in fetch_syms),
               tuple(id(o) for o, _ in program._optimize_ops))
        fn = self._cache.get(sig)
        if fn is None:
            fn = self._compile(program, nodes, leaf_ids, leaf_objs,
                               fetch_syms, state_targets, opt_blobs)
            self._cache[sig] = fn

        def _feed_array(v):
            # device-resident feeds (Tensor / jax array) pass straight
            # through — no device→host→device round trip
            if isinstance(v, Tensor):
                return v.data
            if isinstance(v, jax.Array):
                return v
            return jnp.asarray(np.asarray(v))

        feed_arrays = {k: _feed_array(v) for k, v in feed.items()}
        trainable_ids = {id(t) for t in trainable}
        other_arrays = [t.data for t in leaf_objs
                        if id(t) not in trainable_ids]
        train_arrays = [t.data for t in trainable]
        master_arrays = [
            [opt._master_weights.get(p.name) for p in params]
            for opt, _, params, _, _ in opt_blobs]
        def _lr_array(opt):
            # cache the device scalar: re-uploading an unchanged lr every
            # step costs a host→device transfer
            key = (id(opt), float(opt.get_lr()))
            arr = self._lr_cache.get(key)
            if arr is None:
                if len(self._lr_cache) > 64:   # bound schedule churn
                    self._lr_cache.clear()
                arr = self._lr_cache[key] = jnp.asarray(key[1], jnp.float32)
            return arr

        opt_state_arrays = [
            ([opt._get_state(p) for p in params],
             _lr_array(opt),
             jnp.asarray(opt._step_count + 1, jnp.float32))
            for opt, _, params, _, _ in opt_blobs]

        fetches, state_arrays, new_train, new_masters_all, new_opt_states \
            = fn(feed_arrays, other_arrays, train_arrays, master_arrays,
                 opt_state_arrays)

        # write back state updates and optimizer results; the old param /
        # optimizer-state buffers were donated to XLA, so reassign _data
        # before anything can observe the stale arrays
        for (target, _), arr in zip(program._state_updates, state_arrays):
            target._data = arr
        for t, arr in zip(trainable, new_train):
            t._data = arr
        for (opt, _, params, _, _), sts, new_masters in zip(
                opt_blobs, new_opt_states, new_masters_all):
            opt._step_count += 1
            for p, st, m in zip(params, sts, new_masters):
                opt._accumulators[p.name] = st
                if m is not None:
                    opt._master_weights[p.name] = m

        outs = []
        for f, arr in zip(fetch_syms, fetches):
            outs.append(np.asarray(arr) if return_numpy else Tensor(arr))
        return outs

    def _compile(self, program, nodes, leaf_ids, leaf_objs, fetch_syms,
                 state_targets, opt_blobs):
        trainable_idx = [i for i, t in enumerate(leaf_objs)
                         if isinstance(t, Parameter) and not t.stop_gradient]
        other_idx = [i for i in range(len(leaf_objs))
                     if i not in set(trainable_idx)]
        sym_fetches = [t for t in fetch_syms if isinstance(t, SymbolicTensor)]
        n_fetch = len(sym_fetches)

        def run_fn(feed_arrays, other_arrays, train_arrays, master_arrays,
                   opt_state_arrays):
            env = {("feed", k): v for k, v in feed_arrays.items()}
            for i, arr in zip(other_idx, other_arrays):
                env[("t", id(leaf_objs[i]))] = arr

            if not opt_blobs:
                for i, arr in zip(trainable_idx, train_arrays):
                    env[("t", id(leaf_objs[i]))] = arr
                vals = _eval_graph(nodes, sym_fetches + state_targets, env)
                return (vals[:n_fetch], vals[n_fetch:], list(train_arrays),
                        [], [])

            # Single evaluation: differentiate the first optimizer's loss
            # with the fetches + state updates riding along as aux, so the
            # forward runs once (ref interpretercore.cc:656 — one
            # instruction stream, no re-execution for fetch vars). Targets
            # are DEDUPED by graph node, so the jitted step never returns
            # the same value twice (e.g. a fetched loss that is also the
            # differentiated output).
            def _tkey(s):
                return (id(s._node), s._out_idx) if s._node is not None \
                    else ("feed", s._feed_name)

            loss0 = opt_blobs[0][1]
            aux_targets, aux_pos = [], {}
            for s in [loss0] + sym_fetches + state_targets:
                k = _tkey(s)
                if k not in aux_pos:
                    aux_pos[k] = len(aux_targets)
                    aux_targets.append(s)

            def fwd(p_arrs):
                env2 = dict(env)
                for i, arr in zip(trainable_idx, p_arrs):
                    env2[("t", id(leaf_objs[i]))] = arr
                vals = _eval_graph(nodes, aux_targets, env2)
                return vals[aux_pos[_tkey(loss0)]], vals

            # jax.grad (not value_and_grad): the fetches/states ride as
            # aux and the loss is read from aux too, so the program does
            # not also return the differentiated primal
            grads0, aux = jax.grad(fwd, has_aux=True)(list(train_arrays))

            def _resolve(s):
                return aux[aux_pos[_tkey(s)]]

            fetches = [_resolve(s) for s in sym_fetches]
            state_arrays = [_resolve(s) for s in state_targets]

            new_train = list(train_arrays)
            new_masters_all, new_opt_states = [], []
            for bi, ((opt, loss_sym, params, _, metas), masters,
                     (states, lr, step)) in enumerate(
                    zip(opt_blobs, master_arrays, opt_state_arrays)):
                if bi == 0:
                    grads = grads0
                else:
                    def loss_of(p_arrs, _loss=loss_sym):
                        env2 = dict(env)
                        for i, arr in zip(trainable_idx, p_arrs):
                            env2[("t", id(leaf_objs[i]))] = arr
                        return _eval_graph(nodes, [_loss], env2)[0]
                    grads = jax.grad(loss_of)(list(train_arrays))
                # multi_precision: update the fp32 master, keep the low-
                # precision param as a cast of it (ref adamw multi_precision)
                p_in = [m if m is not None else a
                        for m, a in zip(masters, train_arrays)]
                fused = opt._make_fused(list(metas))
                new_ps, new_sts = fused(p_in, grads, states, lr, step)
                new_masters = []
                for j, (np_, m) in enumerate(zip(new_ps, masters)):
                    if m is not None:
                        new_masters.append(np_)
                        new_train[j] = np_.astype(train_arrays[j].dtype)
                    else:
                        new_masters.append(None)
                        new_train[j] = np_
                new_masters_all.append(new_masters)
                new_opt_states.append(new_sts)
            return (fetches, state_arrays, new_train, new_masters_all,
                    new_opt_states)

        # Donate the params so XLA updates them in place instead of
        # allocating fresh HBM every step (the reference InterpreterCore's
        # buffer-reuse GC, interpretercore.cc:656). ONLY the params are
        # donated: optimizer accumulators and fp32 masters are passed
        # undonated, so each step allocates fresh buffers for them
        # (ROADMAP S5: re-test optimizer-state donation on the real
        # backend). Consequence, same as the reference's static
        # mode: param buffers from BEFORE a run are invalid after it —
        # don't hold detach()/raw-array aliases across exe.run steps
        # (Optimizer.state_dict() returns copies for this reason).
        # FLAGS_static_executor_donate=False restores alias-safe
        # stepping. Feeds and non-trainable leaves are never donated.
        from ..flags import get_flag
        donate = (2,) if get_flag("FLAGS_static_executor_donate") else ()
        if _DONATE_OVERRIDE is not None:    # debugging escape hatch
            donate = _DONATE_OVERRIDE
        return jax.jit(run_fn, donate_argnums=donate)

    def close(self):
        pass
