"""Eager layer-jit capture (framework/layer_jit.py).

ref: /root/reference/paddle/fluid/eager/auto_code_generator/generator/
eager_gen.py:1293 — the reference's answer to eager dispatch overhead is
generated C++; ours is a per-signature compiled capture of the top-level
Layer call. These tests pin the semantics contract: bit-parity with
per-op eager (values, grads, BN buffers, RNG state), hook fallback,
attribute-leak fallback, and signature recompiles."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.framework import layer_jit


@pytest.fixture(autouse=True)
def _flag_on():
    paddle.set_flags({"FLAGS_eager_layer_jit": True})
    yield
    paddle.set_flags({"FLAGS_eager_layer_jit": True})


class Block(nn.Layer):
    def __init__(self, cin=3):
        super().__init__()
        self.conv = nn.Conv2D(cin, 8, 3, padding=1)
        self.bn = nn.BatchNorm2D(8)
        self.drop = nn.Dropout(0.5)

    def forward(self, x):
        return self.drop(
            paddle.nn.functional.relu(self.bn(self.conv(x))))


class Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.b1 = Block(3)
        self.b2 = Block(8)
        self.head = nn.Linear(8 * 4 * 4, 10)

    def forward(self, x):
        h = self.b2(self.b1(x))
        return self.head(paddle.flatten(h, 1))


def _train(flag, steps=3):
    paddle.set_flags({"FLAGS_eager_layer_jit": flag})
    paddle.seed(42)
    np.random.seed(0)
    net = Net()
    opt = paddle.optimizer.Momentum(0.1, momentum=0.9,
                                    parameters=net.parameters())
    x = paddle.to_tensor(np.random.rand(4, 3, 4, 4).astype(np.float32))
    y = paddle.to_tensor(np.random.randint(0, 10, (4,)))
    losses = []
    for _ in range(steps):
        loss = paddle.nn.functional.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    return losses, net


def test_train_parity_with_eager():
    l_jit, net_jit = _train(True)
    l_eager, net_eager = _train(False)
    np.testing.assert_allclose(l_jit, l_eager, rtol=1e-5, atol=1e-6)
    # params, BN running stats identical after 3 dropout-ful steps
    for (n1, p1), (n2, p2) in zip(net_jit.named_parameters(),
                                  net_eager.named_parameters()):
        np.testing.assert_allclose(np.asarray(p1.numpy()),
                                   np.asarray(p2.numpy()),
                                   rtol=1e-5, atol=1e-6, err_msg=n1)
    np.testing.assert_allclose(np.asarray(net_jit.b1.bn._mean.numpy()),
                               np.asarray(net_eager.b1.bn._mean.numpy()),
                               rtol=1e-5, atol=1e-7)


def test_capture_is_used_and_cached():
    paddle.seed(0)
    net = Net()
    x = paddle.to_tensor(np.random.rand(4, 3, 4, 4).astype(np.float32))
    net(x)
    entry = layer_jit._cache.get(net)
    assert entry is not None
    execs = [v for v in entry["execs"].values()
             if v is not layer_jit._UNSAFE]
    assert len(execs) == 1
    net(x)  # second call: same signature, cached
    assert len(entry["execs"]) == 1
    # a new batch size is a new signature
    x2 = paddle.to_tensor(np.random.rand(2, 3, 4, 4).astype(np.float32))
    net(x2)
    assert len(entry["execs"]) == 2


def test_hooks_fall_back_to_eager():
    paddle.seed(0)
    net = Net()
    seen = []
    net.b1.register_forward_post_hook(lambda l, i, o: seen.append(1))
    x = paddle.to_tensor(np.random.rand(2, 3, 4, 4).astype(np.float32))
    net(x)
    entry = layer_jit._cache.get(net)
    assert entry is None or not any(
        v is not layer_jit._UNSAFE for v in entry["execs"].values())
    assert seen  # the hook really ran


def test_attribute_leak_falls_back():
    class Leaky(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lin = nn.Linear(4, 4)
            self.l_aux = None

        def forward(self, x):
            h = self.lin(x)
            self.l_aux = h.mean()   # MoE-style side channel
            return h

    paddle.seed(0)
    net = Leaky()
    x = paddle.to_tensor(np.random.rand(2, 4).astype(np.float32))
    out = net(x)
    # capture must have been rejected; l_aux holds a REAL value
    assert float(net.l_aux.numpy()) == pytest.approx(
        float(np.asarray(out.numpy()).mean()), rel=1e-6)
    entry = layer_jit._cache.get(net)
    assert entry is not None and entry.get("all") is layer_jit._UNSAFE
    # and the child still captures on its own
    net(x)
    lin_entry = layer_jit._cache.get(net.lin)
    assert lin_entry is not None and lin_entry["execs"]


def test_input_grads_flow():
    paddle.seed(0)
    lin = nn.Linear(4, 4)
    x = paddle.to_tensor(np.ones((2, 4), np.float32),
                         stop_gradient=False)
    out = lin(x)
    out.sum().backward()
    assert x.grad is not None
    expect = np.asarray(lin.weight.numpy()).sum(axis=1)
    np.testing.assert_allclose(np.asarray(x.grad.numpy())[0], expect,
                               rtol=1e-5, atol=1e-6)


def test_rng_state_advances_like_eager():
    paddle.seed(7)
    drop = nn.Dropout(0.5)
    x = paddle.to_tensor(np.ones((4, 16), np.float32))
    a1 = np.asarray(drop(x).numpy())
    k_jit = np.asarray(paddle.framework.random.get_rng_state())

    paddle.set_flags({"FLAGS_eager_layer_jit": False})
    paddle.seed(7)
    a2 = np.asarray(drop(x).numpy())
    k_eager = np.asarray(paddle.framework.random.get_rng_state())
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(k_jit, k_eager)


def test_sublayer_eval_retraces():
    # freezing ONE sublayer (net.b1.bn.eval()) must not serve the
    # program traced with it in train mode
    paddle.seed(0)
    net = Net()
    x = paddle.to_tensor(np.random.rand(4, 3, 4, 4).astype(np.float32))
    with paddle.no_grad():
        net(x)
        mean_before = np.asarray(net.b1.bn._mean.numpy()).copy()
        net.b1.bn.eval()          # freeze stats of ONE BN only
        net(x)
        mean_after = np.asarray(net.b1.bn._mean.numpy())
        # frozen BN must NOT have updated its running mean
        np.testing.assert_array_equal(mean_before, mean_after)
        # the unfrozen sibling still updates
        net(x)


def test_integer_output_leaf_keeps_stop_gradient():
    class TopK(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lin = nn.Linear(8, 8)

        def forward(self, x):
            h = self.lin(x)
            idx = paddle.argmax(h, axis=-1)
            return h, idx

    paddle.seed(0)
    net = TopK()
    x = paddle.to_tensor(np.random.rand(2, 8).astype(np.float32))
    h, idx = net(x)
    assert idx.stop_gradient            # int leaf must not ride the tape
    assert not h.stop_gradient
    h.sum().backward()                  # backward through logits works
    assert net.lin.weight.grad is not None


def test_set_flags_is_atomic():
    v0 = paddle.flags.flags_version()
    with pytest.raises(ValueError):
        paddle.set_flags({"FLAGS_tpu_fused_encoder": True,
                          "FLAGS_no_such_flag": 1})
    # nothing applied, no version bump
    assert not paddle.flags.get_flag("FLAGS_tpu_fused_encoder")
    assert paddle.flags.flags_version() == v0


def test_data_dependent_control_flow_falls_back():
    class Branchy(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lin = nn.Linear(4, 4)

        def forward(self, x):
            h = self.lin(x)
            if float(h.sum()) > 0:   # host branch: untraceable
                return h * 2.0
            return h

    paddle.seed(0)
    net = Branchy()
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    out = net(x)          # must not raise; falls back to eager
    assert out.shape == [2, 4]
    entry = layer_jit._cache.get(net)
    assert any(v is layer_jit._UNSAFE for v in entry["execs"].values())


def test_capture_and_op_cache_decline_inside_a_trace():
    """Inside someone's trace neither the layer capture nor the eager
    op-executable cache may engage — the guard was dead code while it
    asked jax for a function that no longer exists. The inputs are
    CONCRETE on purpose: only the trace state can tell."""
    import jax
    from paddle_tpu.framework import op
    lin = nn.Linear(4, 4)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))

    def impl(a):
        return a + 1

    assert op._trace_clean()
    assert layer_jit.try_call(lin, (x,), {})[0]
    assert op._op_exec_for(impl, {}, (x.data,), False) is not None
    seen = {}

    @jax.jit
    def traced(a):
        seen["clean"] = op._trace_clean()
        seen["captured"] = layer_jit.try_call(lin, (x,), {})[0]
        seen["exec"] = op._op_exec_for(impl, {}, (x.data,), False)
        return a

    traced(1.0)
    assert seen == {"clean": False, "captured": False, "exec": None}
