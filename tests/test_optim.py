"""Optimizers decrease loss; gradient compression preserves convergence."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import (adafactor_init, adafactor_update, adamw_init,
                         adamw_update)
from repro.optim.compression import (compress_grads, init_error_state,
                                     quantized_psum)


def _quadratic():
    target = {"w": jnp.asarray([[1.0, -2.0], [3.0, 0.5]]),
              "b": jnp.asarray([0.1, -0.7])}

    def loss(p):
        return (jnp.sum(jnp.square(p["w"] - target["w"]))
                + jnp.sum(jnp.square(p["b"] - target["b"])))

    p0 = {"w": jnp.zeros((2, 2)), "b": jnp.zeros((2,))}
    return loss, p0


def test_adamw_converges():
    loss, p = _quadratic()
    opt = adamw_init(p)
    l0 = float(loss(p))
    for _ in range(200):
        g = jax.grad(loss)(p)
        p, opt = adamw_update(p, g, opt, lr=0.05, weight_decay=0.0)
    assert float(loss(p)) < 0.01 * l0


def test_adafactor_converges():
    loss, p = _quadratic()
    opt = adafactor_init(p)
    l0 = float(loss(p))
    for _ in range(300):
        g = jax.grad(loss)(p)
        p, opt = adafactor_update(p, g, opt, lr=0.05)
    assert float(loss(p)) < 0.05 * l0


def test_compressed_grads_converge():
    loss, p = _quadratic()
    opt = adamw_init(p)
    err = init_error_state(p)
    l0 = float(loss(p))
    for _ in range(200):
        g = jax.grad(loss)(p)
        g, err = compress_grads(g, err)
        p, opt = adamw_update(p, g, opt, lr=0.05, weight_decay=0.0)
    assert float(loss(p)) < 0.02 * l0


def test_compression_error_feedback_unbiased():
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    err = init_error_state(g)
    acc = jnp.zeros((64, 64))
    for _ in range(50):
        dq, err = compress_grads(g, err)
        acc = acc + dq["w"]
    # error feedback: the running mean converges to the true gradient
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g["w"]),
                               atol=2e-3)


def test_quantized_psum_single_device():
    # axis of size 1: quantized psum == identity up to quantization noise
    mesh = jax.make_mesh((1,), ("d",))
    x = jnp.linspace(-3, 3, 128)
    y = jax.shard_map(lambda v: quantized_psum(v, "d"), mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec(),
                      check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=0.05)
