"""Op application: unwrap Tensors -> pure jax impl -> wrap outputs + record tape.

This is the TPU-native analog of the reference's generated ``*_ad_func`` layer
(ref: /root/reference/paddle/fluid/eager/auto_code_generator/generator/
eager_gen.py:1293): AMP autocast, GradNode creation and kernel dispatch all
happen per-op here, except dispatch is simply calling a pure jax function that
XLA compiles/fuses.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from . import autograd

try:  # per-op host profiling hook (the reference's platform::RecordEvent)
    from ..profiler import _host as _prof_host
except Exception:  # pragma: no cover
    _prof_host = None


def unwrap(x):
    from .tensor import Tensor
    if isinstance(x, Tensor):
        return x.data
    return x


def wrap(x, stop_gradient=True):
    from .tensor import Tensor
    return Tensor(x, stop_gradient=stop_gradient)


def _check_nan_inf(op_name, outs):
    """Per-op numerical sanitizer behind FLAGS_check_nan_inf (the TPU analog
    of the reference's post-kernel scan, ref: /root/reference/paddle/fluid/
    framework/operator.cc:2010 + framework/details/nan_inf_utils_detail.cu).

    Device-side reduction (jnp.isfinite(...).all()) then one host sync to
    raise — debug mode only, so the sync is the point."""
    for i, o in enumerate(outs):
        if not hasattr(o, "dtype") or not jnp.issubdtype(o.dtype, jnp.inexact):
            continue  # inexact = floating + complex (fft outputs)
        if isinstance(o, jax.core.Tracer):
            # inside a jit trace the value is symbolic — a host-side bool()
            # would crash the trace. Compiled paths are checked at their
            # concrete boundaries (outputs of the jitted call re-enter apply).
            continue
        if not bool(jnp.isfinite(o).all()):
            n_nan = int(jnp.isnan(o).sum())
            n_inf = int(jnp.isinf(o).sum())
            raise RuntimeError(
                f"Operator {op_name or 'op'} output {i} contains NaN/Inf "
                f"(nan={n_nan}, inf={n_inf}, shape={tuple(o.shape)}, "
                f"dtype={o.dtype}). Triggered by FLAGS_check_nan_inf.")


# ---------------------------------------------------------------------------
# Eager op-executable cache: run each concrete op application as ONE
# compiled XLA call (fwd + residuals; backward a second cached call)
# instead of eagerly launching every jnp primitive inside `impl`. The
# TPU analog of the reference's cached kernel dispatch in the generated
# *_ad_func fast path (eager_gen.py:1293) — every eager primitive is a
# launch of its own, so a 15-primitive op (e.g. cross_entropy) pays 15
# dispatches per call without this.
# ---------------------------------------------------------------------------

_OP_JIT_CACHE: dict = {}
_OP_JIT_MISSES: dict = {}   # impl code object -> distinct keys seen
_OP_JIT_MAX_VARIANTS = 64   # per-call-varying closures: stop compiling


class _OpExec:
    """Compiled fwd(+bwd) pair for one (impl, closure, kwargs, avals)."""

    __slots__ = ("_fwd", "_trees", "_bwds", "with_grad", "broken")

    def __init__(self, impl, kwargs, with_grad):
        self._trees = {}
        self._bwds = {}
        self.with_grad = with_grad
        self.broken = False

        def fwd(*arrays):
            if not with_grad:
                out = impl(*arrays, **kwargs)
                multi = isinstance(out, (tuple, list))
                leaves = tuple(out) if multi else (out,)
                self._trees[(len(leaves), 0)] = (multi, None)
                return leaves, ()
            out, vjp_fn = jax.vjp(lambda *xs: impl(*xs, **kwargs),
                                  *arrays)
            multi = isinstance(out, (tuple, list))
            leaves = tuple(out) if multi else (out,)
            res, res_tree = jax.tree_util.tree_flatten(vjp_fn)
            self._trees[(len(leaves), len(res))] = (multi, res_tree)
            return leaves, tuple(res)

        self._fwd = jax.jit(fwd)

    def run(self, arrays):
        leaves, res = self._fwd(*arrays)
        info = self._trees.get((len(leaves), len(res)))
        if info is None:
            raise RuntimeError("op-exec trace bookkeeping mismatch")
        multi, res_tree = info
        vjp_fn = None
        if self.with_grad:
            bwd = self._bwds.get(len(res))
            if bwd is None:
                def bwd_impl(res_leaves, cots):
                    f = jax.tree_util.tree_unflatten(res_tree,
                                                     list(res_leaves))
                    return tuple(f(cots if multi else cots[0]))
                bwd = jax.jit(bwd_impl)
                self._bwds[len(res)] = bwd

            def vjp_fn(cot):
                cots = cot if isinstance(cot, tuple) else (cot,)
                return bwd(res, tuple(cots))
        return leaves, multi, vjp_fn


def _op_exec_key(impl, kwargs, arrays, needs_grad):
    """Hashable identity of this op application, or None (stay eager):
    the impl's code + closure values + kwargs + input avals. Closures
    holding arrays (e.g. RNG keys drawn per call) are not cacheable."""
    try:
        cells = getattr(impl, "__closure__", None) or ()
        vals = []
        for c in cells:
            v = c.cell_contents
            if isinstance(v, (jax.Array,)) or hasattr(v, "__array__"):
                return None
            hash(v)
            vals.append(v)
        kw = tuple(sorted(kwargs.items()))
        hash(kw)
        metas = tuple(
            (a.shape, str(a.dtype), bool(getattr(a, "weak_type", False)))
            if hasattr(a, "dtype") and hasattr(a, "shape")
            else (type(a).__name__, a)
            for a in arrays)
        hash(metas)
        code = getattr(impl, "__code__", impl)  # ufuncs/partials: self-key
        hash(code)
    except (TypeError, ValueError, AttributeError):
        return None
    return (code, tuple(vals), kw, metas, needs_grad)


def _trace_clean() -> bool:
    """True when no jax trace (jit / grad / vmap / shard_map) is open on
    this thread — the eager op-executable cache and layer-jit capture
    engage only then; inside someone's trace the plain path composes."""
    return jax.core.trace_ctx.is_top_level()


def _op_exec_for(impl, kwargs, arrays, needs_grad):
    from ..flags import get_flag
    if not get_flag("FLAGS_eager_op_jit", True):
        return None
    if not _trace_clean():
        return None  # inside someone's trace: plain path composes fine
    key = _op_exec_key(impl, kwargs, arrays, needs_grad)
    if key is None:
        return None
    code = key[0]
    if _OP_JIT_MISSES.get(code, 0) > _OP_JIT_MAX_VARIANTS:
        return None
    exec_ = _OP_JIT_CACHE.get(key)
    if exec_ is None:
        _OP_JIT_MISSES[code] = _OP_JIT_MISSES.get(code, 0) + 1
        exec_ = _OpExec(impl, kwargs, needs_grad)
        _OP_JIT_CACHE[key] = exec_
    if exec_.broken:
        return None
    return exec_


def _execute(impl, kwargs, arrays, needs_grad):
    """(out, vjp_fn) through the cached op executable, else plain eager."""
    exec_ = _op_exec_for(impl, kwargs, arrays, needs_grad)
    if exec_ is not None:
        try:
            leaves, multi, vjp_fn = exec_.run(arrays)
            return (tuple(leaves) if multi else leaves[0]), vjp_fn
        except Exception:
            exec_.broken = True
    if needs_grad:
        return jax.vjp(lambda *xs: impl(*xs, **kwargs), *arrays)
    return impl(*arrays, **kwargs), None


def apply(impl: Callable, tensor_args: Sequence[Any], kwargs=None,
          differentiable=True, op_name=None):
    """Run `impl(*arrays, **kwargs)` with autograd recording.

    tensor_args: positional inputs that may be Tensor / jax array / numpy /
    python scalar. Non-Tensor entries participate in the computation but
    receive no gradient.
    """
    from .tensor import Tensor
    from ..amp.auto_cast import maybe_cast_inputs

    kwargs = kwargs or {}
    from .symbolic import SymbolicTensor, build_node
    symbolic = any(isinstance(a, SymbolicTensor) for a in tensor_args)
    tensor_args = maybe_cast_inputs(op_name, tensor_args, symbolic=symbolic)
    if symbolic:
        return build_node(impl, tensor_args, kwargs)

    arrays = tuple(unwrap(a) for a in tensor_args)
    input_tensors = [a if isinstance(a, Tensor) else None for a in tensor_args]
    needs_grad = (
        differentiable
        and autograd.tape_enabled()
        and any(t is not None and not t.stop_gradient for t in input_tensors)
    )

    if _prof_host is not None and _prof_host.enabled:
        import time as _time
        _t0 = _time.perf_counter_ns()
        out, vjp_fn = _execute(impl, kwargs, arrays, needs_grad)
        _prof_host.events.append((op_name or getattr(impl, "__name__", "op"),
                                  _t0, _time.perf_counter_ns()))
    else:
        out, vjp_fn = _execute(impl, kwargs, arrays, needs_grad)

    multi = isinstance(out, (tuple, list))
    outs = list(out) if multi else [out]
    from ..flags import get_flag
    if get_flag("FLAGS_check_nan_inf"):
        _check_nan_inf(op_name or getattr(impl, "__name__", None), outs)
    out_tensors = [wrap(o, stop_gradient=not needs_grad) for o in outs]
    if needs_grad:
        autograd.record(vjp_fn, input_tensors, out_tensors, multi=multi)
    return tuple(out_tensors) if multi else out_tensors[0]


def apply_inplace(target, impl: Callable, tensor_args: Sequence[Any],
                  kwargs=None, differentiable=True):
    """In-place variant: rebinds target._data to the op result.

    The tape records the target Tensor object as re-produced; the backward
    walk resolves versions by reverse execution order (see autograd).
    """
    from .tensor import Tensor
    from .symbolic import SymbolicTensor, build_node

    kwargs = kwargs or {}
    if any(isinstance(a, SymbolicTensor) for a in tensor_args):
        out = build_node(impl, tensor_args, kwargs)
        if isinstance(target, SymbolicTensor):
            target._node = out._node
            target._out_idx = out._out_idx
            target._aval = out._aval
            return target
        raise RuntimeError("in-place op on a concrete Tensor with symbolic "
                           "inputs is not supported in static mode")

    arrays = tuple(unwrap(a) for a in tensor_args)
    input_tensors = [a if isinstance(a, Tensor) else None for a in tensor_args]
    needs_grad = (
        differentiable
        and autograd.tape_enabled()
        and any(t is not None and not t.stop_gradient for t in input_tensors)
    )
    if needs_grad:
        out, vjp_fn = jax.vjp(lambda *xs: impl(*xs, **kwargs), *arrays)
    else:
        out = impl(*arrays, **kwargs)
    target._data = out
    if needs_grad:
        target.stop_gradient = False
        autograd.record(vjp_fn, input_tensors, [target])
    return target
