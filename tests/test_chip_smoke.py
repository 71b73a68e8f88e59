"""Rehearsal of ``chip_smoke.py`` on the CPU mesh, so the script that
proves the chip path cannot rot between chip runs: its phase functions
run at toy sizes (the Pallas kernels in interpret mode), a failing phase
fails the script, and the script itself refuses to run without a TPU."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SERVING_TOY = dict(
    d_model=64, heads=4, ffn=128, layers=2, vocab=97, block_size=4,
    num_blocks=64, max_blocks_per_seq=16, max_batch=4, n_requests=3,
    prompt_lo=6, prompt_hi=24, shared_prefix=8, new_tokens=5,
    prefill_token_budget=16, kv_dtype="float32", expect_kernel=False,
    logits_tol=chip_smoke.LOGITS_TOL_CPU)
TRAINER_TOY = dict(hidden=64, inter=128, heads=4, vocab=256, layers=2,
                   batch=4, seq=32, steps=5, dtype="float32",
                   expect_kernel=False)


@pytest.fixture
def kernel_path(monkeypatch):
    """Put the serving engine on the path it takes on the chip: packed
    ragged steps and the kernel side of the paged views' seam, the
    kernel interpreted."""
    from paddle_tpu.framework import device, op
    monkeypatch.setattr(device, "use_pallas_kernels", lambda: True)
    # the phase counts kernel launches at trace time: start without the
    # executables an earlier test may have cached for the same shapes
    op._OP_JIT_CACHE.clear()


def test_serving_phase_rehearsal(kernel_path):
    res = chip_smoke.serving_phase(**SERVING_TOY)
    assert res["packed_steps"] > 0 and res["mixed_steps"] > 0
    assert res["logits_rel_err"] <= chip_smoke.LOGITS_TOL_CPU


def test_serving_phase_failure_propagates(kernel_path, monkeypatch):
    """An injected failure inside a phase raises out of it — nothing in
    the script turns a failed phase into a printed field."""
    from paddle_tpu.inference.recovery import RecoverableServer

    def boom(self):
        raise RuntimeError("injected step failure")
    monkeypatch.setattr(RecoverableServer, "step", boom)
    with pytest.raises(RuntimeError, match="injected step failure"):
        chip_smoke.serving_phase(**SERVING_TOY)


def test_trainer_phase_rehearsal():
    res = chip_smoke.trainer_phase(**TRAINER_TOY)
    assert res["losses"][-1] < res["losses"][0]


def test_four_device_phases_rehearsal():
    """The four-chip phase on four of the suite's virtual CPU devices."""
    res = chip_smoke.trainer_four_chip_phase(
        **dict(TRAINER_TOY, layers=4, steps=3, loss_tol=1e-4))
    assert res["delta"] <= 1e-4
    res = chip_smoke.serving_phase(
        mp=4, **dict(SERVING_TOY, n_requests=2))
    assert res["packed_steps"] > 0


def test_kernel_phase_rehearsal(monkeypatch):
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    rows = chip_smoke.kernel_phase(
        heads=4, head_dim=16, block_size=4, num_blocks=64, max_blocks=16,
        batch=4, chunk=16, d_model=128, vocab=300, moe_experts=4,
        moe_d=128, moe_ffn=128, norm_rows=64, flash_seq=64, flash_heads=2,
        adam_shape=(64, 64), pool_dtype="float32", expect_kernel=False,
        tol=1e-4)
    assert len(rows) >= 12
    assert not any(r["mosaic"] for r in rows)       # interpreted here


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins; otherwise the cache sits at
    ``<checkout>/.jax_cache`` whatever the cwd, pid or time."""
    from paddle_tpu.framework import device
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    assert device.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.compile_cache_dir() == "/somewhere/else"

    # and what ``import paddle_tpu`` does with it: sets the directory when
    # the variable is unset, leaves jax's own reading alone when it is set
    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/sentinel")
        device.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "/sentinel"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        device.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _recorded_summary():
    from benchmark import xplane
    return xplane.summarize(os.path.join(ROOT, "benchmark", "testdata",
                                         "small.xplane.pb"))


def test_largest_device_operation_is_reported(capsys):
    """The reduction the serving and decoder phases print after their
    traced steps, on the recorded trace the benchmark's tests use."""
    summary = _recorded_summary()
    name, share = chip_smoke._report_largest_op("serving", summary, [])
    assert summary["op_seconds"][name] == max(
        summary["op_seconds"].values())
    assert 0.0 < share <= 100.0
    assert f"largest device operation: {name}" in capsys.readouterr().out


def test_a_pool_sized_copy_fails_the_smoke():
    summary = _recorded_summary()
    summary["op_seconds"]["copy_bf16_3072_2_32_16_128_"] = 1e-3
    chip_smoke._report_largest_op("serving", summary,
                                  [(3072, 2, 8, 16, 128)])
    with pytest.raises(AssertionError, match="pool-sized copy is back"):
        chip_smoke._report_largest_op("serving", summary,
                                      [(3072, 2, 32, 16, 128)])
