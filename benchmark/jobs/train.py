"""Training job: ``LlamaSpmdTrainer`` as ``bench.py`` configures it, on the
mesh the configuration names, one synchronised step after another.

``--seed`` draws the weights and the token ids of a few batches, made on the
device before the window and cycled. The work of a step does not depend on
them.
"""
from __future__ import annotations

import importlib
import math
import time

from benchmark import metrics, xplane

# First-step loss of the trainer (bf16 weights and activations) against the
# float32 reference on the same weights and batch, absolute, in nats. bf16
# moves a token's loss by ~1e-2 with random sign, and the mean over 32k tokens
# by ~1e-4 (PR 23's scratch: 11.19346 against 11.19354). A dropped layer, a
# wrong mask or rotation, or a missing all-reduce re-draws the logits of a
# randomly initialised model: ~1e-2 and more.
LOSS_TOL = 2e-3
BATCHES = 8


def build_trainer(config: dict, traffic: dict, seed: int, chips: int):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.llama_spmd import LlamaSpmdTrainer
    from paddle_tpu.parallel import mesh as mesh_mod
    mesh_mod.build_mesh(**config["mesh"], devices=jax.devices()[:chips])
    cfg = LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=traffic["seq"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"])
    opt = dict(config["trainer"])
    for key in ("compute_dtype", "moments_dtype"):
        opt[key] = getattr(jnp, opt[key])
    return LlamaSpmdTrainer(cfg, seed=seed % 2**31, **opt)


def make_batches(traffic: dict, vocab: int, seed: int) -> list:
    """``BATCHES`` batches of token ids, drawn on the device in one call and
    placed as the trainer places its input."""
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import mesh as mesh_mod
    shape = (BATCHES, traffic["batch"], traffic["seq"])
    ids = jax.jit(lambda k: jax.random.randint(k, shape, 0, vocab))(
        jax.random.PRNGKey((seed + 1) % 2**31))
    return [mesh_mod.shard_tensor_data(ids[i], P("dp", None))
            for i in range(BATCHES)]


def required_flops_per_token(config: dict, seq: int) -> float:
    """Forward plus backward FLOPs a token needs, recompute not counted
    (``LlamaSpmdTrainer.flops_per_token``'s convention, copied): 6 per matmul
    parameter with the readout counted once and the embedding gather not at
    all, plus causal attention, 6 * layers * hidden * seq."""
    h, f = config["hidden_size"], config["intermediate_size"]
    kv = h // config["num_attention_heads"] * config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    matmul = layers * (2 * h * h + 2 * h * kv + 3 * h * f) \
        + config["vocab_size"] * h
    return 6.0 * matmul + 6.0 * layers * h * seq


def run(config: dict, traffic: dict, *, seed: int, seconds: float,
        chips: int = 1, tracer=None, log=print, on_open=None,
        loss_tol: float = LOSS_TOL) -> dict:
    import jax
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    tracer = tracer or xplane.NoTracer()
    trainer = build_trainer(config, traffic, seed, chips)
    batches = make_batches(traffic, config["vocab_size"], seed)
    want = ref.loss(ref.weights_of(trainer), batches[0],
                    heads=config["num_attention_heads"],
                    kv_heads=config["num_key_value_heads"],
                    theta=config["rope_theta"], eps=config["rms_norm_eps"],
                    window=config["sliding_window"])
    losses, step_ends, step_ms = [], [], []

    def step():
        ids = batches[len(losses) % BATCHES]
        t0 = time.perf_counter()
        with tracer.span("bench.step"):
            loss = jax.block_until_ready(trainer.train_step(ids))
        step_ends.append(time.perf_counter())
        step_ms.append((step_ends[-1] - t0) * 1e3)
        losses.append(loss)

    step()                                     # compiles
    got = float(losses[0])
    log(f"[train] first-step loss {got:.5f}, reference {want:.5f}")
    faults = []
    if not abs(got - want) <= loss_tol:
        faults.append(f"first-step loss {got} against the reference {want}: "
                      f"off by more than {loss_tol}")
    for _ in range(traffic["warmup_steps"]):
        step()
    warm = len(losses)
    if on_open:
        on_open()
    t_open = time.perf_counter()
    tracer.start()
    while time.perf_counter() - t_open < seconds:
        step()
        tracer.tick(len(losses) - warm)
    tracer.tick(len(losses) - warm, last=True)
    win = [float(x) for x in losses[warm:]]
    bad = sum(not math.isfinite(x) for x in win)
    if bad:
        faults.append(f"{bad} steps with a loss that is not finite")
    tokens = traffic["batch"] * traffic["seq"]
    e2e = metrics.train_metrics(step_ends[warm:], tokens, t_open)
    log(f"[train] window: {e2e.pop('_samples')}, last loss {win[-1]:.4f}")
    for f in faults:
        log(f"[train] FAULT {f}")
    return {
        "e2e": e2e,
        "t_open": t_open,
        "correct": not faults,
        "attempted": len(win),
        "failed": bad,
        "series": {"train_step_ms": step_ms[warm:]},
        "counters": {
            "steps": len(win), "first_loss": got, "reference_loss": want,
            "tokens_per_step": tokens, "chips": chips,
            "flops_per_token": required_flops_per_token(config,
                                                        traffic["seq"])},
    }
