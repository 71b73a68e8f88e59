"""Eager layer-jit: a transparent compiled boundary for dygraph Layer calls.

The reference keeps eager mode fast with generated C++ fast paths so
per-op dispatch never dominates (ref: /root/reference/paddle/fluid/eager/
auto_code_generator/generator/eager_gen.py:1293, python_c_gen.py:90 —
GIL-released `eager_api_*` + `*_ad_func`). The TPU-native answer is
coarser and stronger: the FIRST Layer.__call__ on the stack captures the
whole sub-tree's forward as ONE cached XLA program per input signature,
and registers ONE autograd-tape node whose vjp is a second cached
program (two-phase: the forward returns the vjp residual LEAVES, the
backward re-unflattens them under its own stable jit — so weights ride
as arguments, never baked constants).

Semantics preserved relative to plain per-op eager:
  * RNG: the capture threads the live generator key through the program
    in split-chain mode and writes the advanced key back — random draws
    and generator state match the uncaptured run bit-for-bit.
  * Buffers (BN running stats): new values are returned as aux outputs
    and written back into the buffer tensors after each call.
  * Fallbacks: any trace failure (data-dependent Python control flow),
    forward hooks anywhere in the sub-tree, or a traced value leaking
    into a layer attribute during capture (e.g. MoE's `l_aux`) reverts
    the layer to per-op eager while its CHILDREN still capture
    individually on later calls.

Not supported under capture (use FLAGS_eager_layer_jit=0 to disable
globally): double backward through the captured region (grad-of-grad).
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from . import autograd
from . import random as _random
from .op import _trace_clean

_UNSAFE = "unsafe"


class _State(threading.local):
    def __init__(self):
        self.active = False   # a capture trace is running


_state = _State()

# layer -> {"execs": {sig: _LayerExec | _UNSAFE}, "all": _UNSAFE?}
_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def enabled() -> bool:
    from ..flags import get_flag
    return bool(get_flag("FLAGS_eager_layer_jit"))


def mark_unsafe(layer) -> None:
    """Permanently exclude ``layer`` from whole-forward capture; it (and
    only it — children still capture individually) runs per-op eager.

    For layers whose forward is side-effectful by design (e.g.
    inference/moe_serving.py accumulates per-expert load counters into
    layer attributes): the capture would trace once, detect the tracer
    leak, and fall back anyway — opting out up front skips the wasted
    trace AND keeps the leak from ever poisoning the attribute state."""
    _cache[layer] = {"execs": {}, "all": _UNSAFE}


def _flatten(obj):
    """Flatten a nest of Tensors/arrays; literals ride in the treedef.
    Returns (leaves, tree, objs) — objs[i] is the source Tensor for leaf
    i, or None for a raw array leaf."""
    from .tensor import Tensor
    leaves: List[Any] = []
    objs: List[Any] = []

    def walk(o):
        if isinstance(o, Tensor):
            leaves.append(o.data)
            objs.append(o)
            return ("T", len(leaves) - 1)
        if isinstance(o, (jax.Array, jax.core.Tracer)):
            leaves.append(o)
            objs.append(None)
            return ("A", len(leaves) - 1)
        import numpy as _np
        if isinstance(o, _np.ndarray):
            # a literal ndarray would explode the signature repr
            raise TypeError("ndarray in layer-jit capture tree")
        if isinstance(o, (list, tuple)):
            return (type(o).__name__, [walk(v) for v in o])
        if isinstance(o, dict):
            return ("dict", [(k, walk(v)) for k, v in o.items()])
        return ("L", o)

    tree = walk(obj)
    return leaves, tree, objs


def _unflatten(tree, leaves, wrap):
    kind = tree[0]
    if kind == "T":
        return wrap(leaves[tree[1]], tree[1])
    if kind == "A":
        return leaves[tree[1]]
    if kind in ("list", "tuple"):
        seq = [_unflatten(t, leaves, wrap) for t in tree[1]]
        return seq if kind == "list" else tuple(seq)
    if kind == "dict":
        return {k: _unflatten(t, leaves, wrap) for k, t in tree[1]}
    return tree[1]


def _walk_layers(layer):
    yield layer
    for _, sub in layer.named_sublayers():
        yield sub


def _scan_tracer_leak(layer) -> Optional[str]:
    """During a capture trace: any layer attribute holding a traced value
    outside the swapped-and-restored _parameters/_buffers means the
    forward has host-visible side effects the capture cannot preserve."""
    from .tensor import Tensor

    def holds_tracer(v, depth=2):
        arr = v.data if isinstance(v, Tensor) else v
        if isinstance(arr, jax.core.Tracer):
            return True
        if depth <= 0:
            return False
        if isinstance(v, (list, tuple)):
            return any(holds_tracer(e, depth - 1) for e in v)
        if isinstance(v, dict):
            return any(holds_tracer(e, depth - 1) for e in v.values())
        vd = getattr(v, "__dict__", None)
        if vd is not None and not isinstance(v, (Tensor, type)) \
                and not hasattr(v, "_sub_layers"):
            return any(holds_tracer(e, depth - 1) for e in vd.values())
        return False

    for sub in _walk_layers(layer):
        registered = {id(v) for v in sub._parameters.values()}
        registered |= {id(v) for v in sub._buffers.values()}
        for k, v in vars(sub).items():
            if k in ("_parameters", "_buffers", "_sub_layers"):
                continue
            if id(v) in registered:
                continue  # attribute alias of a registered param/buffer
            if holds_tracer(v):
                return f"{type(sub).__name__}.{k}"
    return None


class _CaptureUnsafe(Exception):
    pass


def _restore_snapshot(snap):
    for sub, d in snap:
        sub.__dict__.clear()
        sub.__dict__.update(d)


class _LayerExec:
    """Compiled fwd(+bwd) pair for one (layer, input signature)."""

    def __init__(self, layer, with_grad: bool, in_tree, kwargs_tuple):
        # weakref: _cache is a WeakKeyDictionary keyed by the layer, so
        # the exec (its value) must not strongly reference it or the
        # entry (and its compiled executables) can never be collected
        self._layer_ref = weakref.ref(layer)
        self.with_grad = with_grad
        self.in_tree = in_tree
        self.kwargs = dict(kwargs_tuple)
        # The exec holds NO parameter or buffer: jax keeps every jitted
        # function — here the bound ``_fwd_impl``, hence this object —
        # alive in its own cache, so a strong reference from here would
        # pin a dead model's weights in device memory for the life of
        # the process. ``call`` reads them off the live layer and lends
        # them to the trace for the duration of the call.
        self._live = None   # (diff_params, nd_params, buffers) in a call
        # Host-side trees are PER TRACE: the same jit can hold several
        # traced programs (aval changes retrace silently, and a retrace
        # may take a different Python path — e.g. a model flag toggled
        # between calls). Key by (n_out_leaves, n_res_leaves) so each
        # call looks up the trees of the program that actually ran.
        self._trees = {}   # (n_out, n_res) -> (out_tree, res_tree, leak)
        self._bwds = {}    # n_res -> jitted backward for that res_tree
        self._trace_out_tree = None
        self._trace_leak = None
        self._trace_diffable = None
        self._fwd = self._jitted()

    def _jitted(self):
        """What ``call`` launches with the operands."""
        return jax.jit(self._fwd_impl)

    @property
    def layer(self):
        layer = self._layer_ref()
        if layer is None:  # caller always holds the layer during a call
            raise ReferenceError("captured layer was garbage-collected")
        return layer

    # -- forward ------------------------------------------------------------
    def _run(self, diff_arrays, in_leaves, nd_arrays, buf_arrays, key):
        """Pure apply: swap arrays into the live objects, run forward
        under no_grad with chained RNG, collect outs + new buffers."""
        from .tensor import Tensor
        layer = self.layer
        diff_params, nd_params, buffers = self._live
        saved_d = [p._data for p in diff_params]
        saved_n = [p._data for p in nd_params]
        saved_b = [b._data for b in buffers]
        for p, a in zip(diff_params, diff_arrays):
            p._data = a
        for p, a in zip(nd_params, nd_arrays):
            p._data = a
        for b, a in zip(buffers, buf_arrays):
            b._data = a
        try:
            args = _unflatten(self.in_tree, list(in_leaves),
                              lambda a, i: Tensor(a, stop_gradient=True))
            with autograd.no_grad(), _random.chain_scope(key) as chain:
                out = layer.forward(*args, **self.kwargs)
                new_key = chain.current()  # before scope restore
            new_bufs = tuple(b._data for b in buffers)
            out_leaves, out_tree, out_objs = _flatten(out)
            self._trace_out_tree = out_tree
            # integer/bool outputs (indices, masks) cannot ride the tape;
            # backward must feed their vjp float0 cotangents
            self._trace_diffable = tuple(
                (bool(jnp.issubdtype(o.dtype, jnp.inexact)),
                 tuple(o.shape)) for o in out_leaves)
            leak = None
            if self.with_grad and any(o is None for o in out_objs):
                leak = "non-Tensor output leaf"  # cannot ride the tape
            if leak is None:
                leak = _scan_tracer_leak(layer)
            self._trace_leak = leak
            return tuple(out_leaves), (new_bufs, new_key)
        finally:
            for p, a in zip(diff_params, saved_d):
                p._data = a
            for p, a in zip(nd_params, saved_n):
                p._data = a
            for b, a in zip(buffers, saved_b):
                b._data = a

    def _fwd_impl(self, diff_arrays, in_leaves, nd_arrays, buf_arrays,
                  key):
        if not self.with_grad:
            outs, aux = self._run(diff_arrays, in_leaves, nd_arrays,
                                  buf_arrays, key)
            self._trees[(len(outs), 0)] = (self._trace_out_tree, None,
                                           self._trace_leak,
                                           self._trace_diffable)
            return outs, aux, ()

        def run(diff, ins):
            return self._run(diff, ins, nd_arrays, buf_arrays, key)

        outs, vjp_fn, aux = jax.vjp(run, tuple(diff_arrays),
                                    tuple(in_leaves), has_aux=True)
        res_leaves, res_tree = jax.tree_util.tree_flatten(vjp_fn)
        self._trees[(len(outs), len(res_leaves))] = (
            self._trace_out_tree, res_tree, self._trace_leak,
            self._trace_diffable)
        return outs, aux, tuple(res_leaves)

    # -- backward -----------------------------------------------------------
    def _bwd_for(self, res_tree, n_res, diffable):
        bwd = self._bwds.get(n_res)
        if bwd is None:
            import numpy as _np

            def bwd_impl(res_leaves, cot_leaves):
                vjp_fn = jax.tree_util.tree_unflatten(res_tree,
                                                      list(res_leaves))
                it = iter(cot_leaves)
                cots = tuple(
                    next(it) if d
                    else _np.zeros(shape, jax.dtypes.float0)
                    for d, shape in diffable)
                d_diff, d_in = vjp_fn(cots)
                return tuple(d_diff), tuple(d_in)
            bwd = jax.jit(bwd_impl)
            self._bwds[n_res] = bwd
        return bwd

    # -- entry --------------------------------------------------------------
    def call(self, in_leaves, in_objs, named):
        """``named``: the layer's ``named_parameters()`` as of this call
        (the walk ``try_call`` already made for the signature)."""
        from .tensor import Tensor
        layer = self.layer
        diff_params = [p for _, p in named if not p.stop_gradient]
        nd_params = [p for _, p in named if p.stop_gradient]
        buffers = [b for _, b in layer.named_buffers() if b is not None]
        diff_arrays = tuple(p.data for p in diff_params)
        nd_arrays = tuple(p.data for p in nd_params)
        buf_arrays = tuple(b.data for b in buffers)
        key = _random.get_rng_state()
        # Any call may trace (first call, or a silent jax retrace on an
        # aval change), and a trace runs the Python forward, which may
        # mutate layer attributes with trace-time values (observer
        # stats, side channels). Snapshot every sublayer's __dict__ so a
        # failed or leaky capture restores pre-call state before the
        # eager re-run (a stale tracer left in an attribute poisons
        # later eager ops).
        snap = [(sub, dict(vars(sub)))
                for sub in _walk_layers(layer)]
        _state.active = True
        self._live = (diff_params, nd_params, buffers)
        try:
            outs, (new_bufs, new_key), res = self._fwd(
                diff_arrays, tuple(in_leaves), nd_arrays, buf_arrays,
                key)
        except Exception:
            _restore_snapshot(snap)
            raise
        finally:
            _state.active = False
            self._live = None
        info = self._trees.get((len(outs), len(res)))
        if info is None or info[2] is not None:
            _restore_snapshot(snap)
            raise _CaptureUnsafe(info[2] if info else
                                 "trace bookkeeping mismatch")
        out_tree, res_tree, _, diffable = info
        _random.set_rng_state(new_key)
        for b, a in zip(buffers, new_bufs):
            b._data = a

        grad_on = self.with_grad
        out_tensors: List[Any] = []

        def wrap(a, i):
            d = diffable[i][0]
            t = Tensor(a, stop_gradient=not (grad_on and d))
            out_tensors.append((t, d))
            return t

        result = _unflatten(out_tree, list(outs), wrap)
        node_outs = [t for t, d in out_tensors if d]
        if grad_on and node_outs:
            node_inputs = list(diff_params) + list(in_objs)
            bwd = self._bwd_for(res_tree, len(res), diffable)

            def node_vjp(cot):
                cots = cot if isinstance(cot, tuple) else (cot,)
                d_diff, d_in = bwd(res, tuple(cots))
                return list(d_diff) + list(d_in)

            autograd.record(node_vjp, node_inputs, node_outs,
                            multi=len(node_outs) > 1)
        return result


def _walk_info(layer):
    """One subtree walk per call: hook presence + EVERY sublayer's
    training flag (freezing one BN via net.sub.eval() must retrace —
    the top-level flag alone would serve the stale program)."""
    hooks = False
    training = []
    for sub in _walk_layers(layer):
        if sub._forward_pre_hooks or sub._forward_post_hooks:
            hooks = True
        training.append(bool(getattr(sub, "training", True)))
    return hooks, tuple(training)


def _signature(named, in_leaves, in_objs, kwargs_tuple, with_grad,
               in_tree, training):
    from ..flags import flags_version
    parts = [with_grad, training,
             kwargs_tuple, repr(in_tree), flags_version()]
    for a, o in zip(in_leaves, in_objs):
        parts.append((tuple(a.shape), str(a.dtype),
                      o.stop_gradient if o is not None else True))
    for _, p in named:
        parts.append((tuple(p.shape), str(p.dtype), p.stop_gradient))
    return tuple(parts)


def try_call(layer, inputs, kwargs):
    """Fast-path attempt from Layer.__call__. Returns (handled, result)."""
    from .symbolic import SymbolicTensor
    from .tensor import Tensor

    if _state.active or not enabled() or not _trace_clean():
        return False, None

    entry = _cache.get(layer)
    if entry is not None and entry.get("all") is _UNSAFE:
        return False, None

    kw_items = []
    for k, v in kwargs.items():
        if isinstance(v, (Tensor, jax.Array)):
            return False, None
        try:
            hash(v)
        except TypeError:
            return False, None
        kw_items.append((k, v))
    kwargs_tuple = tuple(sorted(kw_items))

    any_tensor = False
    for a in inputs:
        if isinstance(a, SymbolicTensor):
            return False, None
        if isinstance(a, Tensor):
            if isinstance(a.data, jax.core.Tracer):
                return False, None
            any_tensor = True
        elif a is not None and not isinstance(a, (bool, int, float, str,
                                                  list, tuple, dict)):
            return False, None
    if not any_tensor:
        return False, None

    hooks, training = _walk_info(layer)
    if hooks:
        return False, None

    try:
        in_leaves, in_tree, in_objs = _flatten(list(inputs))
    except Exception:
        return False, None
    if any(isinstance(a, jax.core.Tracer) for a in in_leaves):
        return False, None

    named = list(layer.named_parameters())
    with_grad = autograd.tape_enabled() and (
        any(not p.stop_gradient for _, p in named)
        or any(o is not None and not o.stop_gradient for o in in_objs))

    if entry is None:
        entry = {"execs": {}}
        _cache[layer] = entry
    sig = _signature(named, in_leaves, in_objs, kwargs_tuple, with_grad,
                     in_tree, training)
    exec_ = entry["execs"].get(sig)
    if exec_ is _UNSAFE:
        return False, None
    if exec_ is None:
        exec_ = _LayerExec(layer, with_grad, in_tree, kwargs_tuple)
        entry["execs"][sig] = exec_
    try:
        return True, exec_.call(in_leaves, in_objs, named)
    except _CaptureUnsafe:
        entry["execs"].pop(sig, None)
        entry["all"] = _UNSAFE
        return False, None
    except Exception:
        # data-dependent control flow, unsupported internals, …:
        # permanent per-signature fallback to per-op eager
        import os
        if os.environ.get("PADDLE_TPU_LAYER_JIT_DEBUG"):
            raise
        entry["execs"][sig] = _UNSAFE
        return False, None


# --- captures that carry state held outside the layer ------------------
# The serving step: ``model(x, caches=views, time_step=t)`` reads and
# writes a paged cache's K/V pools through the views. ``try_call``
# declines it (the views are no hashable keyword), so every op of the
# forward used to be a program of its own. ``call_with_state`` captures
# the same forward whole: the owner of the state lends it to the trace.
#
# The ``state`` protocol (inference/paged_cache.py ``_LentStep``):
#   key                    static, hashable: what shapes the program
#                          besides the operands' shapes
#   arrays()               -> (donated, plain): pytrees of arrays. The
#                          donated ones are DONATED to the program and
#                          come back as its outputs
#   lend(donated, plain)   context manager, entered inside the trace:
#                          binds the (traced) arrays where the forward
#                          will find them, yields ``(kwargs, collect)``
#                          -- the keyword arguments that carry the state
#                          into ``forward`` and a function returning the
#                          donated arrays' new values -- and takes every
#                          binding back when it exits, also when the
#                          forward raises
#   rebind(donated)        after the program ran: the owner takes the
#                          new arrays (the old ones are deleted)

CORE = "core"                    # the layer's forward does not capture
TRACE_FAILED = "trace_failed"    # this shape's first trace raised


def _plain_literals(tree):
    """``tree`` with every literal that is not plain data dropped: an
    exec outlives its layer (jax keeps jitted functions alive), so the
    output tree it keeps must not hold the caller's objects (cache
    views, and through them the pools)."""
    kind, val = tree
    if kind in ("list", "tuple"):
        return (kind, [_plain_literals(t) for t in val])
    if kind == "dict":
        return (kind, [(k, _plain_literals(t)) for k, t in val])
    if kind == "L" and not isinstance(val, (bool, int, float, str,
                                            type(None))):
        return (kind, None)
    return tree


class _StateExec(_LayerExec):
    """The compiled forward of one (layer, input signature, state key),
    run without grad (the serving step). Weights, buffers and the RNG
    key ride as operands exactly as in ``_LayerExec``; the state's
    arrays ride in front of them."""

    def __init__(self, layer, in_tree):
        # lent for the duration of one call, never kept (see _LayerExec:
        # the exec is immortal and must pin nothing)
        self.state = None
        self.returned = None
        super().__init__(layer, False, in_tree, ())

    def _jitted(self):
        self._program = jax.jit(self.fwd, donate_argnums=(0,))
        return self._launch

    def fwd(self, donated, plain, *operands):
        # the NAME is read: the compiled module is ``jit_fwd`` and its
        # kernel launches ``fwd.N``, as the op executables' are
        # (framework/op.py), and the benchmark's readers find the
        # paged-attention launch by that (``mosaic:fwd_*``)
        with self.state.lend(donated, plain) as (kwargs, collect):
            self.kwargs = kwargs
            try:
                outs, aux, _ = self._fwd_impl(*operands)
            finally:
                self.kwargs = {}
            return outs, aux, collect()

    def _run(self, *operands):
        outs, aux = super()._run(*operands)
        self._trace_out_tree = _plain_literals(self._trace_out_tree)
        return outs, aux

    def _launch(self, *operands):
        outs, aux, self.returned = self._program(
            *self.state.arrays(), *operands)
        return outs, aux, ()


def state_programs(layer) -> int:
    """Programs ``call_with_state`` holds for ``layer`` so far."""
    from ..nn import Layer
    entry = _cache.get(layer) if isinstance(layer, Layer) else None
    return 0 if entry is None else sum(
        isinstance(e, _StateExec) for e in entry["execs"].values())


def call_with_state(layer, inputs, state):
    """Run ``layer.forward(*inputs, **kwargs)`` as ONE program, where
    ``kwargs`` and the arrays behind them are ``state``'s (protocol
    above). Returns ``(result, None)``, or ``(None, reason)`` when the
    forward does not capture and the caller should run it per op:
    ``CORE`` by the rule ``try_call`` applies to a layer (an
    ``nn.Layer``, not ``mark_unsafe``, no forward hooks, a trace that
    leaves no tracer behind, the layer-jit on and no outer trace), and
    ``TRACE_FAILED`` for a signature whose first trace raised: that
    signature stays per-op, others still capture. The state is whole
    either way: a failed trace donated nothing, and a program that ran
    has its outputs rebound before anything else happens."""
    from ..nn import Layer
    if not isinstance(layer, Layer) or _state.active or not enabled() \
            or not _trace_clean():
        return None, CORE
    entry = _cache.setdefault(layer, {"execs": {}})
    hooks, training = _walk_info(layer)
    if hooks or _UNSAFE in (entry.get("all"), entry.get("state")):
        return None, CORE
    in_leaves, in_tree, in_objs = _flatten(list(inputs))
    named = list(layer.named_parameters())
    sig = _signature(named, in_leaves, in_objs, ("state", state.key),
                     False, in_tree, training)
    exec_ = entry["execs"].get(sig)
    if exec_ is _UNSAFE:
        return None, TRACE_FAILED
    if exec_ is None:
        exec_ = entry["execs"][sig] = _StateExec(layer, in_tree)
    exec_.state = state
    try:
        return exec_.call(in_leaves, in_objs, named), None
    except _CaptureUnsafe:
        # the program ran (its outputs are rebound below) but the trace
        # left a tracer in the layer: the run is discarded, and the
        # caller's per-op run repeats the step's writes, row for row
        # the same (and is the one that is counted)
        del entry["execs"][sig]
        entry["state"] = _UNSAFE
        return None, CORE
    except Exception:
        import os
        if os.environ.get("PADDLE_TPU_LAYER_JIT_DEBUG"):
            raise
        entry["execs"][sig] = _UNSAFE
        return None, TRACE_FAILED
    finally:
        returned, exec_.state, exec_.returned = exec_.returned, None, None
        if returned is not None:
            state.rebind(returned)
