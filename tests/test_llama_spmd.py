"""Flagship LlamaSpmdTrainer / spmd_pipeline tests.

Loss-equivalence contract mirrors the reference's hybrid-parallel tests
(ref: /root/reference/python/paddle/fluid/tests/unittests/collective/fleet/
hybrid_parallel_pp_transformer.py — PP loss must equal serial loss): the
pipelined, sharded forward/backward must match a serial single-device run
of the same weights.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.llama_spmd import LlamaSpmdTrainer
from paddle_tpu.parallel import mesh as mesh_mod


CFG = dict(vocab=128, hidden=32, layers=4, heads=4, kv_heads=2, inter=64,
           seq=32)


def _make_cfg(seq=32):
    c = dict(CFG)
    c["seq"] = seq
    return LlamaConfig.tiny(**c)


def _serial_params_from(params):
    """Collapse [pp, lps, ...] block stacking to [1, pp*lps, ...]."""
    blocks = {k: np.asarray(v).reshape((1, -1) + v.shape[2:])
              for k, v in params["blocks"].items()}
    out = {k: np.asarray(v) for k, v in params.items() if k != "blocks"}
    out["blocks"] = blocks
    return out


def _place_tree(trainer, raw):
    """Re-place raw numpy params with the (new) trainer's shardings."""
    placed = jax.tree_util.tree_map(
        lambda tgt, src: jax.device_put(jnp.asarray(src), tgt.sharding),
        trainer.params, raw)
    return placed


@pytest.fixture
def restore_mesh():
    yield
    mesh_mod.build_mesh(dp=1, devices=jax.devices()[:1])


@pytest.mark.parametrize("deg", [
    {"dp": 2, "pp": 2, "sharding": 1, "sep": 1, "mp": 2},
    {"dp": 1, "pp": 2, "sharding": 2, "sep": 2, "mp": 1},
])
def test_hybrid_forward_and_grads_match_serial(deg, restore_mesh):
    seq = 32 * deg["sep"]
    cfg = _make_cfg(seq)
    mesh_mod.build_mesh(**deg)
    n_micro = 2 * deg["pp"]
    trainer = LlamaSpmdTrainer(cfg, n_micro=n_micro,
                               compute_dtype=jnp.float32, seed=0)
    batch = max(4, n_micro)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq))

    logits = np.asarray(jax.jit(trainer.forward)(trainer.params,
                                                 jnp.asarray(ids)))
    loss, grads = jax.jit(jax.value_and_grad(trainer.loss_fn))(
        trainer.params, jnp.asarray(ids), jnp.asarray(ids))
    loss = float(loss)
    raw_params = _serial_params_from(
        jax.tree_util.tree_map(np.asarray, trainer.params))

    # serial single-device reference with identical weights
    mesh_mod.build_mesh(dp=1, devices=jax.devices()[:1])
    ref = LlamaSpmdTrainer(cfg, n_micro=1, compute_dtype=jnp.float32, seed=0)
    ref_params = _place_tree(ref, raw_params)
    ref_logits = np.asarray(jax.jit(ref.forward)(ref_params,
                                                 jnp.asarray(ids)))
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref.loss_fn))(
        ref_params, jnp.asarray(ids), jnp.asarray(ids))

    np.testing.assert_allclose(logits, ref_logits, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(loss, float(ref_loss), atol=1e-5, rtol=1e-5)

    ref_grads_np = jax.tree_util.tree_map(np.asarray, ref_grads)
    # compare grads leaf-by-leaf (block leaves need the stage reshape)
    for key in ("embed", "head", "norm"):
        np.testing.assert_allclose(
            np.asarray(grads[key]), np.asarray(ref_grads_np[key]),
            atol=3e-4, rtol=3e-4)
    for name, g in grads["blocks"].items():
        g = np.asarray(g).reshape(np.asarray(
            ref_grads_np["blocks"][name]).shape)
        np.testing.assert_allclose(
            g, np.asarray(ref_grads_np["blocks"][name]),
            atol=3e-4, rtol=3e-4, err_msg=f"grad mismatch: blocks[{name}]")


def test_train_step_loss_decreases_under_hybrid(restore_mesh):
    deg = {"dp": 1, "pp": 2, "sharding": 2, "sep": 2, "mp": 1}
    cfg = _make_cfg(seq=64)
    mesh_mod.build_mesh(**deg)
    trainer = LlamaSpmdTrainer(cfg, n_micro=4, lr=1e-3,
                               compute_dtype=jnp.float32, seed=0)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (8, 64))
    losses = [float(trainer.train_step(ids)) for _ in range(6)]
    assert losses[-1] < losses[0], losses


def test_save_dots_remat_matches_full(restore_mesh):
    """remat_policy='save_dots' must give identical grads to 'full' remat."""
    cfg = _make_cfg()
    mesh_mod.build_mesh(dp=1, devices=jax.devices()[:1])
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, (4, 32))

    def grads_for(policy):
        t = LlamaSpmdTrainer(cfg, compute_dtype=jnp.float32, seed=0,
                             remat_policy=policy)
        _, g = jax.jit(jax.value_and_grad(t.loss_fn))(
            t.params, jnp.asarray(ids), jnp.asarray(ids))
        return jax.tree_util.tree_map(np.asarray, g)

    g_full = grads_for("full")
    g_dots = grads_for("save_dots")
    for a, b in zip(jax.tree_util.tree_leaves(g_full),
                    jax.tree_util.tree_leaves(g_dots)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    with pytest.raises(ValueError):
        LlamaSpmdTrainer(cfg, remat_policy="dots")


def test_zero_sharding_actually_partitions_opt_state(restore_mesh):
    """ZeRO: optimizer moments must be sharded over the 'sharding' axis
    (per-device bytes < replicated bytes)."""
    deg = {"dp": 1, "pp": 1, "sharding": 2, "sep": 1, "mp": 1}
    cfg = _make_cfg()
    mesh_mod.build_mesh(**deg)
    trainer = LlamaSpmdTrainer(cfg, compute_dtype=jnp.float32, seed=0)
    sharded_leaves = 0
    for st in jax.tree_util.tree_leaves(
            trainer.opt_state,
            is_leaf=lambda x: isinstance(x, dict) and "m" in x):
        if not isinstance(st, dict):
            continue
        m = st["m"]
        shard_bytes = [d.data.nbytes for d in m.addressable_shards]
        if sum(shard_bytes) == m.nbytes and len(shard_bytes) > 1 and \
                max(shard_bytes) < m.nbytes:
            sharded_leaves += 1
    assert sharded_leaves > 0, "no optimizer state leaf is ZeRO-sharded"


def test_spmd_pipeline_matches_sequential_map(restore_mesh):
    """spmd_pipeline output == applying stages sequentially, and its AD
    gradient matches the sequential gradient."""
    mesh_mod.build_mesh(pp=2, devices=jax.devices()[:2])
    from paddle_tpu.parallel.pipeline import spmd_pipeline
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((2, 8, 8), dtype=np.float32))
    x = jnp.asarray(rng.standard_normal((4, 2, 8), dtype=np.float32))

    def stage_fn(p, xb):
        return jnp.tanh(xb @ p)

    def sequential(W, x):
        def one(xb):
            for s in range(2):
                xb = stage_fn(W[s], xb)
            return xb
        return jax.vmap(one)(x)

    def fix_stage_fn(p, xb):
        return jnp.tanh(xb @ p["w"])

    out_pipe = jax.jit(lambda W, x: spmd_pipeline(fix_stage_fn, {"w": W},
                                                  x))(W, x)
    out_seq = sequential(W, x)
    np.testing.assert_allclose(np.asarray(out_pipe), np.asarray(out_seq),
                               atol=1e-6)

    g_pipe = jax.jit(jax.grad(lambda W: jnp.sum(
        spmd_pipeline(fix_stage_fn, {"w": W}, x) ** 2)))(W)
    g_seq = jax.grad(lambda W: jnp.sum(sequential(W, x) ** 2))(W)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               atol=1e-5)


def test_flash_kernel_runs_per_shard_under_pp_and_mp(restore_mesh,
                                                     monkeypatch):
    """The chip's multi-chip attention path on the CPU mesh. Mosaic
    kernels are not partitioned automatically, so under pp=2 x mp=2 the
    flash kernel must run per shard inside a shard_map (nested in the
    pipeline's, manual on 'pp') that leaves NO mesh axis automatic — the
    Mosaic lowering's condition, size-1 axes included. Here the repo's
    own kernel runs interpreted; the loss must equal the one-device loss
    of the same seed, where the kernel is called directly."""
    import paddle_tpu.ops.pallas.flash_attention as fa
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models import llama_spmd
    monkeypatch.setattr(llama_spmd, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    seen = []
    real = fa.flash_attention_blhd

    def spy(q, k, v, **kw):
        m = mesh_mod._current_mesh()
        seen.append((sorted(mesh_mod._manual_axes(m)), q.shape[2]))
        return real(q, k, v, **kw)
    monkeypatch.setattr(fa, "flash_attention_blhd", spy)
    set_flags({"FLAGS_tpu_flash_impl": "native"})
    try:
        cfg = LlamaConfig.tiny(vocab=128, hidden=256, layers=4, heads=4,
                               kv_heads=4, inter=128, seq=128)
        ids = np.random.RandomState(0).randint(0, 128, (4, 128))

        def first_loss(**mesh):
            mesh_mod.build_mesh(devices=jax.devices()[:4 if mesh else 1],
                                **(mesh or {"dp": 1}))
            t = LlamaSpmdTrainer(cfg, compute_dtype=jnp.float32,
                                 remat=False, seed=0)
            return float(t.train_step(ids))

        serial = first_loss()
        assert seen and seen[-1] == ([], 4)      # called directly, 4 heads
        del seen[:]
        hybrid = first_loss(pp=2, mp=2)
        # every call saw every mesh axis manual, and this shard's half of
        # the heads
        assert seen and all(s == (sorted(mesh_mod.AXIS_ORDER), 2)
                            for s in seen)
        assert abs(hybrid - serial) < 1e-4 + 1e-4 * abs(serial)
    finally:
        set_flags({"FLAGS_tpu_flash_impl": "jax"})
