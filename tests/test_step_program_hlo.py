"""The step programs, compiled for the v5e without one.

A model call of the paged engine on the chip is ONE program
(``paged_cache.model_call``): all layers of
``FusedMultiTransformer.forward``, each layer's page-form K/V append and
its paged-attention launch, the pools donated through it. These tests
build the decode-only and a mixed step program at ``gpt3-6.7b.chat``'s
shapes with abstract weights and pools, compile them for the described
chip and read the HLO: no ``copy`` / ``transpose`` of a pool's shape,
every pool aliased to its output, no constant above 1 MB (weights are
operands, never constants: 3.2 GB of them), one ``tpu_custom_call`` a
layer. In the style of ``test_pool_write_hlo.py``, whose readings they
share."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.framework import device, layer_jit, random
from paddle_tpu.framework.tensor import Parameter, Tensor
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import paged_cache as pc

from tests.test_pool_write_hlo import _pool_sized_moves, one_chip  # noqa: F401

D, HEADS, FF, LAYERS = 4096, 32, 16384, 4
BLOCKS, BS, SLOTS, MB = 3072, 16, 32, 128
POOL = (BLOCKS, 2, HEADS, BS, D // HEADS)


def _abstract(shape, dtype):
    t = Tensor(0.0)
    t._data = jax.ShapeDtypeStruct(tuple(int(s) for s in shape),
                                   jnp.dtype(dtype))
    return t


@pytest.fixture
def chat(monkeypatch):
    """(model, cache) at chat's shapes, every weight and pool a
    ``ShapeDtypeStruct``: nothing of their size is allocated."""
    def parameter(self, shape, attr=None, dtype=None, is_bias=False,
                  default_initializer=None):
        p = Parameter(0.0)
        p._data = jax.ShapeDtypeStruct(tuple(int(s) for s in shape),
                                       jnp.float32)
        return p
    monkeypatch.setattr(nn.Layer, "create_parameter", parameter)
    monkeypatch.setattr(
        paddle, "zeros", lambda shape, dtype=None: _abstract(shape, dtype))
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    kernel = importlib.import_module(
        "paddle_tpu.ops.pallas.paged_attention")
    monkeypatch.setattr(kernel, "on_tpu", lambda: True)
    model = FusedMultiTransformer(D, HEADS, FF, num_layers=LAYERS)
    cache = pc.PagedKVCache.for_model(
        model, BS, BLOCKS, SLOTS, max_blocks_per_seq=MB, dtype="bfloat16")
    return model, cache


def _lower(model, cache, views, rows, one_chip):
    """The program ``call_with_state`` would launch for this call,
    lowered for the described chip: the exec it would build, handed the
    operands ``_LayerExec.call`` would hand it, abstract."""
    x = _abstract(rows + (D,), jnp.float32)
    t = Tensor(np.zeros(rows[0], np.int32))
    exec_ = layer_jit._StateExec(model, layer_jit._flatten([x])[1])
    exec_.state = pc._LentStep(x, views, t)
    params = [p for _, p in model.named_parameters()]
    exec_._live = live = (
        [p for p in params if not p.stop_gradient],
        [p for p in params if p.stop_gradient],
        [b for _, b in model.named_buffers() if b is not None])
    diff, nd, bufs = (tuple(p.data for p in group) for group in live)
    operands = exec_.state.arrays() + (
        diff, (x.data,), nd, bufs, random.get_rng_state())
    lowered = exec_._program.lower(*jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        operands))
    # lowering ran the trace: nothing of it may stay behind
    assert all(isinstance(p.data, jax.ShapeDtypeStruct)
               for p in cache.pools)
    return lowered.compile().as_text()


def _constants_above(hlo: str, limit: int) -> list:
    found = []
    for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* constant\(", hlo):
        dims = [int(d) for d in m.group(2).split(",") if d]
        # s32, bf16, f32, u8, pred: the width is the digits
        size = int(np.prod(dims)) * max(
            1, int(re.sub(r"\D", "", m.group(1)) or 8) // 8)
        if size > limit:
            found.append((m.group(0), size))
    return found


def _views(cache, kind):
    if kind == "decode":
        for slot in range(SLOTS):
            cache.ensure(slot, 200 + slot)
        return cache.views, (SLOTS, 1)
    # a mixed step of chat: two 128-token chunks and the decode rows
    cache.ensure(0, 128, write_from=0)
    cache.ensure(1, 256, write_from=128)
    for slot in range(2, SLOTS):
        cache.ensure(slot, 200 + slot)
    mask = np.zeros(SLOTS, bool)
    mask[:2] = True
    cache.set_decode_mask(mask)
    lens = np.array([0, 0] + [199 + s for s in range(2, SLOTS)])
    views = cache.ragged_views([("prefill", 0, 0, 128, 0),
                                ("prefill", 1, 128, 128, 0),
                                ("decode", lens, 1)])
    return views, (1, 256 + SLOTS)


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_step_program_moves_no_pool_and_holds_no_weight(chat, one_chip,
                                                        kind):
    model, cache = chat
    views, rows = _views(cache, kind)
    hlo = _lower(model, cache, views, rows, one_chip)
    assert not _pool_sized_moves(hlo, POOL), _pool_sized_moves(hlo, POOL)
    assert not _constants_above(hlo, 1 << 20), _constants_above(hlo, 1 << 20)
    assert hlo.count('custom_call_target="tpu_custom_call"') == LAYERS
    tile = 1 if kind == "decode" else 64
    # the kernel's result keeps the name and the shape its readers find
    # it by (benchmark/xplane.short_name -> ``mosaic:fwd_f32_..``)
    assert re.search(r"%%fwd\S* = f32\[\d+,%d,%d,128\]\S* custom-call\("
                     % (HEADS, tile), hlo)
    head = hlo.splitlines()[0]
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}", head)
    assert len(aliased) >= LAYERS, head[:400]
    params = {int(m.group(2)) for m in re.finditer(
        r"= bf16\[%s\]\S* parameter\((\d+)\)" % ",".join(map(str, POOL)),
        hlo) for m in [re.match(r"(.*)\((\d+)\)$", m.group(0))]}
    assert len(params) == LAYERS
    assert params <= {int(p) for _, p in aliased}, (params, aliased)


def test_the_reading_finds_a_closed_over_weight(one_chip):
    """The control: a weight a program closes over is a constant of its
    size in the compiled module, and the reading above sees it."""
    w = np.random.RandomState(0).randn(1024, 1024).astype(np.float32)

    def closed(x):
        return x @ jnp.asarray(w)

    hlo = jax.jit(closed).lower(jax.ShapeDtypeStruct(
        (8, 1024), jnp.float32, sharding=one_chip)).compile().as_text()
    assert [size for _, size in _constants_above(hlo, 1 << 20)] == [4 << 20]


# ---- the config-driven block's step program ----------------------------
# ``DecoderCore`` offers its forward pure (``layer_jit._PureExec``): the
# same reading at three decoder cells' shapes, weights and pools abstract,
# the state store real (a few MB). A cell's state store is written back
# by a gather, whose result the compiler lays out once more (a transpose
# of the store's shape from the gather, one a conv layer, as the per-op
# mix program has it); nothing copies the lent store itself.

DECODER_CELLS = {
    "busy-chat": ("lfm2-24b-a2b-1chip", "serve_conv"),
    "mixed-queue": ("trinity-large-ep8", "serve_arch"),
    "long-decode": ("joyai-llm-flash-1chip", "serve_latent"),
}


def _decoder_program(monkeypatch, one_chip, cell, kind, tmp_path):
    """(compiled HLO, cache, config) of the step program a decoder cell's
    ``kind`` call (decode-only, or a packed step: a whole prompt chunk
    beside every other slot's decode row) runs, weights and pools
    abstract."""
    import functools
    import json
    import os
    from paddle_tpu.inference import decoder
    name, job = DECODER_CELLS[cell]
    job = importlib.import_module(f"benchmark.jobs.{job}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    cfg = decoder.DecoderConfig.from_spec(
        job.server_spec(config, 3, str(tmp_path)))
    draw = decoder._draw_params
    monkeypatch.setattr(decoder, "_draw_params", lambda c, seed: (
        jax.eval_shape(functools.partial(draw, c, seed))))
    monkeypatch.setattr(
        paddle, "zeros", lambda shape, dtype=None: _abstract(shape, dtype))
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    for kernel in ("paged_attention", "grouped_gemm"):
        monkeypatch.setattr(importlib.import_module(
            f"paddle_tpu.ops.pallas.{kernel}"), "on_tpu", lambda: True)
    core = decoder.DecoderCore(cfg, 3)
    e = config["engine"]
    slots = e["max_batch"]
    cache = pc.PagedKVCache.for_model(
        core, e["block_size"], e["num_blocks"], slots,
        max_blocks_per_seq=e["max_blocks_per_seq"], dtype=e["kv_dtype"])
    if kind == "decode":
        for slot in range(slots):
            cache.ensure(slot, 200 + slot)
        views, rows = cache.views, (slots, 1)
    else:
        chunk = e["prefill_token_budget"]
        cache.ensure(0, chunk, write_from=0)
        for slot in range(1, slots):
            cache.ensure(slot, 200 + slot)
        mask = np.zeros(slots, bool)
        mask[0] = True
        cache.set_decode_mask(mask)
        lens = np.array([0] + [199 + s for s in range(1, slots)])
        views = cache.ragged_views([("prefill", 0, 0, chunk, 0),
                                    ("decode", lens, 1)])
        rows = (1, chunk + slots)
    x = _abstract(rows + (cfg.hidden_size,), jnp.float32)
    exec_ = layer_jit._PureExec(core)
    exec_.state = state = pc._LentStep(
        x, views, Tensor(np.zeros(rows[0], np.int32)))
    operands = state.arrays() + core.step_operands(x, **state.kwargs) + (
        (x.data,),)
    # at the chip's precision: this suite's "highest" asks the grouped
    # GEMM for float32 passes over bfloat16, which Mosaic refuses
    with jax.default_matmul_precision("default"):
        lowered = exec_.program.lower(*jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), operands))
        hlo = lowered.compile().as_text()
    assert all(isinstance(p.data, jax.ShapeDtypeStruct)
               for p in cache.pools)
    assert cache._lent_mix is None
    return hlo, cache, cfg


def _parameters_of(hlo, shape, dtype="bf16"):
    """The entry computation's parameters of ``shape``."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    return {int(m.group(1)) for m in re.finditer(
        r"= %s\[%s\]\S* parameter\((\d+)\)"
        % (dtype, ",".join(map(str, shape))), entry)}


@pytest.mark.parametrize("cell, kind", [
    ("busy-chat", "decode"), ("busy-chat", "mixed"),
    ("mixed-queue", "decode"), ("long-decode", "decode")])
def test_decoder_step_program_moves_no_pool_or_state_and_holds_no_weight(
        monkeypatch, one_chip, tmp_path, cell, kind):
    hlo, cache, cfg = _decoder_program(monkeypatch, one_chip, cell, kind,
                                       tmp_path)
    pool = tuple(cache.pools[0].data.shape)
    assert not _pool_sized_moves(hlo, pool), _pool_sized_moves(hlo, pool)
    assert not _constants_above(hlo, 1 << 20), _constants_above(hlo, 1 << 20)
    attn = len(cache.kv_layers)
    experts = sum(cfg.is_moe(i) for i in range(cfg.num_layers))
    assert hlo.count('custom_call_target="tpu_custom_call"') == \
        attn + 2 * experts
    # the launches keep the names their readers find them by
    assert len(re.findall(r"%fwd\S* = \S+ custom-call\(", hlo)) == attn
    assert len(re.findall(r"%gmm\S* = \S+ custom-call\(", hlo)) == \
        2 * experts
    head = hlo.splitlines()[0]
    aliased = {int(p) for _, p in re.findall(r"\{(\d+)\}: \((\d+), \{\}",
                                             head)}
    lent = _parameters_of(hlo, pool)
    assert len(lent) == attn and lent <= aliased, (lent, head[:400])
    if cache.state_layers:
        shape = tuple(cache.state[0].shape)
        moves = _pool_sized_moves(hlo, shape)
        assert len(moves) == len(cache.state_layers), moves
        assert all("transpose(%gather" in m for m in moves), moves
        stores = _parameters_of(hlo, shape)
        assert len(stores) == len(cache.state_layers)
        assert stores <= aliased, (stores, head[:400])
