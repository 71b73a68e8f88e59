"""paddle.inference (ref: /root/reference/paddle/fluid/inference/api/
analysis_predictor.cc — AnalysisPredictor::Run:1071, ZeroCopyRun:2044;
python surface python/paddle/inference/).

The reference's deployment pipeline (analysis passes → IR fusions → TRT
subgraphs → NaiveExecutor) maps to: load the saved program, capture it
under one jit (XLA is the analysis+fusion pipeline), run. Config knobs
route to real behavior:

  * switch_ir_optim(True)   → forward captured via jit.to_static (one
                              fused XLA program). False = eager per-op
                              dispatch (the reference's un-fused
                              NaiveExecutor mode, useful for debugging).
  * enable_tpu(precision)   → Bfloat16/Half casts parameters, buffers and
                              float inputs to the serving dtype; Int8
                              rewrites FusedMultiTransformer blocks to
                              FusedMultiTransformerInt8 (weight-only MXU
                              int8, ref fused_multi_transformer_int8_op).
  * enable_memory_optim()   → host input staging buffers are dropped
                              after each run and outputs are fetched
                              straight to host (no device-side cache) —
                              the reference's memory-optimize pass frees
                              activation buffers the same way.
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional

import numpy as np

from ..framework.tensor import Tensor

from .serving import (ContinuousBatchingEngine,  # noqa: F401
                      ParallelStats, PrefillStats, PrefixCacheStats,
                      ResilienceStats, ShardedServingCore,
                      SpecDecodeStats, TenantStats)
from .telemetry import (MetricsRegistry, NetStats,  # noqa: F401
                        StatsBase, TraceCollector)
from .accounting import (CostLedger, WorkModel,  # noqa: F401
                         WASTE_CAUSES)
from .monitor import (Alert, HealthMonitor,  # noqa: F401
                      HealthReport, SeriesBuffer, SloPolicy,
                      SloTracker)
from .paged_cache import (BlockAllocator, BlockOOM,  # noqa: F401
                          PagedKVCache, PagedLayerCache,
                          PagedPrefillView,
                          chain_block_hashes, chain_hash)
from .resilience import (CrashInjector, EngineCrash,  # noqa: F401
                         FaultInjector, NetworkFaultInjector,
                         RequestOutcome, RouterFaultInjector)
from .scheduler import (DEFAULT_TENANT,  # noqa: F401
                        MIN_PREFILL_SUFFIX_ROWS,
                        PagedRequest, PagedServingEngine, Tenant,
                        chunked_prefill)
from .speculative import (SpeculativeEngine,  # noqa: F401
                          TokenServingModel, branch_lane_seed,
                          logit_mask_fn, register_logit_mask)
from .moe_serving import (MoeServingCore,  # noqa: F401
                          moe_capacity)
from .decoder import DecoderConfig, DecoderCore  # noqa: F401
from .recovery import (SNAPSHOT_VERSION,  # noqa: F401
                       RecoverableServer, RecoveryError,
                       RequestJournal, SnapshotVersionError,
                       load_snapshot, read_journal, save_snapshot)
from .router import (EngineWorker, InProcWorker,  # noqa: F401
                     PipeWorker, Router, RouterStats, WorkerDied,
                     WorkerError, WorkerTimeout,
                     build_model_from_spec, build_server_from_spec,
                     token_chain_hashes)
from .net import (ReplyCache, ResilientTransport,  # noqa: F401
                  SocketHost)
from .fleet import (FleetSupervisor, MigrationPolicy,  # noqa: F401
                    SocketWorker)

__all__ = ["Config", "Predictor", "create_predictor", "PrecisionType",
           "PlaceType", "Alert", "ContinuousBatchingEngine",
           "BlockAllocator", "CostLedger", "WorkModel", "WASTE_CAUSES",
           "BlockOOM", "CrashInjector", "EngineCrash", "FaultInjector",
           "HealthMonitor", "HealthReport", "SeriesBuffer",
           "SloPolicy", "SloTracker",
           "MetricsRegistry", "MoeServingCore", "moe_capacity",
           "DecoderConfig", "DecoderCore",
           "PagedKVCache",
           "PagedLayerCache", "PagedPrefillView", "PagedRequest",
           "PagedServingEngine", "ParallelStats", "PrefillStats",
           "PrefixCacheStats",
           "RecoverableServer", "RecoveryError", "RequestJournal",
           "RequestOutcome", "ResilienceStats", "SNAPSHOT_VERSION",
           "ShardedServingCore",
           "SnapshotVersionError", "SpecDecodeStats",
           "SpeculativeEngine", "StatsBase", "Tenant",
           "TenantStats", "TokenServingModel", "TraceCollector",
           "DEFAULT_TENANT",
           "MIN_PREFILL_SUFFIX_ROWS", "chunked_prefill",
           "branch_lane_seed", "logit_mask_fn", "register_logit_mask",
           "chain_block_hashes", "chain_hash", "load_snapshot",
           "read_journal", "save_snapshot",
           "EngineWorker", "InProcWorker", "PipeWorker", "Router",
           "RouterFaultInjector", "RouterStats", "WorkerDied",
           "WorkerError", "WorkerTimeout", "build_model_from_spec",
           "build_server_from_spec", "token_chain_hashes",
           "FleetSupervisor", "MigrationPolicy", "SocketWorker",
           "NetStats", "NetworkFaultInjector", "ReplyCache",
           "ResilientTransport", "SocketHost"]


class PrecisionType:
    Float32 = 0
    Half = 1
    Bfloat16 = 2
    Int8 = 3


class PlaceType:
    CPU = 0
    GPU = 1
    TPU = 4


class Config:
    def __init__(self, prog_file=None, params_file=None):
        self.prog_file = prog_file
        self.params_file = params_file
        self._use_tpu = True
        self._precision = PrecisionType.Float32
        self._memory_pool_mb = 0
        self._memory_optim = False
        self._ir_optim = True
        self._cpu_threads = None

    def set_prog_file(self, path):
        self.prog_file = path

    def set_params_file(self, path):
        self.params_file = path

    def enable_use_gpu(self, memory_pool_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._precision = precision

    def enable_tpu(self, precision=PrecisionType.Bfloat16):
        self._use_tpu = True
        self._precision = precision

    def disable_gpu(self):
        pass

    def enable_memory_optim(self, flag=True):
        self._memory_optim = bool(flag)

    def memory_optim_enabled(self):
        return self._memory_optim

    def switch_ir_optim(self, flag=True):
        self._ir_optim = bool(flag)

    def ir_optim(self):
        return self._ir_optim

    def enable_tensorrt_engine(self, *a, **kw):
        # TensorRT is CUDA-only; XLA applies its own fusion. Accepted
        # no-op — precision still routes through enable_tpu/enable_use_gpu.
        precision = kw.get("precision_mode", kw.get("precision"))
        if precision is not None:
            self._precision = precision

    def set_cpu_math_library_num_threads(self, n):
        # XLA host thread pools are fixed at backend init; record the
        # request so launchers can export it before process start.
        self._cpu_threads = int(n)
        import os
        os.environ["PADDLE_TPU_HOST_THREADS"] = str(int(n))


class _Handle:
    def __init__(self, name, predictor, is_input):
        self.name = name
        self._pred = predictor
        self._is_input = is_input

    def copy_from_cpu(self, data):
        self._pred._inputs[self.name] = np.asarray(data)

    def reshape(self, shape):
        pass

    def copy_to_cpu(self):
        return self._pred._outputs[self.name]

    def share_external_data(self, data):
        self.copy_from_cpu(np.asarray(data))


def _cast_layer_floats(layer, np_dtype):
    """Serving-precision cast: parameters + float buffers."""
    from ..framework import autograd
    with autograd.no_grad():
        for p in layer.parameters():
            if np.issubdtype(np.dtype(str(p.data.dtype)), np.floating):
                p._data = p.data.astype(np_dtype)
        for b in layer.buffers():
            if b is not None and hasattr(b, "data") and \
                    np.issubdtype(np.dtype(str(b.data.dtype)),
                                  np.floating):
                b._data = b.data.astype(np_dtype)


def _quantize_fused_blocks(layer):
    """Int8 precision: rewrite FusedMultiTransformer blocks to the
    weight-only int8 variant. Returns (count, new_top) — new_top
    replaces `layer` when the loaded model IS a bare
    FusedMultiTransformer (no parent slot to assign into)."""
    from ..incubate.nn.fused_transformer import (FusedMultiTransformer,
                                                 FusedMultiTransformerInt8)
    count = 0
    new_top = layer
    if isinstance(layer, FusedMultiTransformer) and \
            not isinstance(layer, FusedMultiTransformerInt8):
        return 1, FusedMultiTransformerInt8.from_float(layer)
    for owner in [layer] + [l for _, l in layer.named_sublayers()]:
        for name, child in list(getattr(owner, "_sub_layers", {}).items()):
            if isinstance(child, FusedMultiTransformer) and \
                    not isinstance(child, FusedMultiTransformerInt8):
                setattr(owner, name,
                        FusedMultiTransformerInt8.from_float(child))
                count += 1
    return count, new_top


class Predictor:
    """Runs a paddle_tpu.jit-saved model (ref AnalysisPredictor)."""

    def __init__(self, config: Config):
        from .. import jit
        path = config.prog_file
        if path and path.endswith(".pdmodel"):
            path = path[:-len(".pdmodel")]
        self._layer = jit.load(path)
        self._inputs: Dict[str, np.ndarray] = {}
        self._outputs: Dict[str, np.ndarray] = {}
        self._input_names = ["input_" + str(i) for i in range(8)]
        self._output_names: List[str] = []
        self._precision = config._precision
        self._memory_optim = config._memory_optim
        self._ir_optim = config._ir_optim
        self._np_dtype = np.float32

        inner = self._layer._inner
        if self._precision == PrecisionType.Bfloat16:
            import jax.numpy as jnp
            self._np_dtype = jnp.bfloat16
            _cast_layer_floats(inner, self._np_dtype)
        elif self._precision == PrecisionType.Half:
            self._np_dtype = np.float16
            _cast_layer_floats(inner, self._np_dtype)
        elif self._precision == PrecisionType.Int8:
            n, inner = _quantize_fused_blocks(inner)
            self._layer._inner = inner
            if n == 0:
                warnings.warn(
                    "PrecisionType.Int8: no FusedMultiTransformer blocks "
                    "found to quantize; running float (per-layer PTQ "
                    "lives in paddle.quantization)")
        if self._ir_optim:
            # the analysis/fusion pipeline: one compiled XLA program
            self._runner = jit.to_static(inner)
        else:
            self._runner = inner

    def get_input_names(self):
        return self._input_names

    def get_input_handle(self, name):
        return _Handle(name, self, True)

    def get_output_names(self):
        return self._output_names

    def get_output_handle(self, name):
        return _Handle(name, self, False)

    def _wrap_input(self, a):
        arr = np.asarray(a.numpy() if hasattr(a, "numpy") else a)
        if self._precision in (PrecisionType.Bfloat16, PrecisionType.Half) \
                and np.issubdtype(arr.dtype, np.floating):
            arr = arr.astype(self._np_dtype)
        return Tensor(arr)

    def run(self, inputs: Optional[List] = None):
        if inputs is not None:
            args = [self._wrap_input(a) for a in inputs]
        else:
            args = [self._wrap_input(self._inputs[n])
                    for n in self._input_names if n in self._inputs]
        from ..framework.autograd import no_grad
        with no_grad():
            out = self._runner(*args)
        outs = out if isinstance(out, (list, tuple)) else [out]
        self._output_names = [f"output_{i}" for i in range(len(outs))]
        self._outputs = {n: np.asarray(o.numpy())
                         for n, o in zip(self._output_names, outs)}
        if self._memory_optim:
            # free the host staging copies; device buffers die with the
            # last Tensor reference when `outs`/`args` go out of scope
            self._inputs.clear()
        if inputs is not None:
            return [self._outputs[n] for n in self._output_names]
        return True

    def zero_copy_run(self):
        return self.run()


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)
