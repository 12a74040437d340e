"""Pallas residual-join kernel vs the XLA oracle (fwd + grad) — the
56×56-stage experiment's correctness gate; perf verdict lives in
scripts/pallas_residual_experiment.py / the root PERF.md."""

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.ops.elementwise import residual_relu


def test_residual_relu_matches_xla(rng):
    x = jnp.asarray(rng.normal(size=(4, 8, 8, 256)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(4, 8, 8, 256)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(residual_relu(x, y)),
        np.asarray(jax.nn.relu(x + y)),
        rtol=1e-6,
    )


def test_residual_relu_gradients(rng):
    x = jnp.asarray(rng.normal(size=(2, 4, 4, 128)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(2, 4, 4, 128)), jnp.float32)

    def loss_pallas(a, b):
        return jnp.sum(residual_relu(a, b) ** 2)

    def loss_xla(a, b):
        return jnp.sum(jax.nn.relu(a + b) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1))(x, y)
    gx = jax.grad(loss_xla, argnums=(0, 1))(x, y)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_resnet_block_pallas_join_matches(rng):
    """A ResNet block with residual_join='pallas' computes the same
    function as the default."""
    from horovod_tpu.models.resnet import ResNet18

    x = jnp.asarray(rng.uniform(size=(2, 32, 32, 3)), jnp.float32)
    out = {}
    for join in ("xla", "pallas"):
        model = ResNet18(num_classes=10, dtype=jnp.float32,
                         residual_join=join)
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        out[join] = np.asarray(
            model.apply(variables, x, train=False), np.float32
        )
    np.testing.assert_allclose(out["pallas"], out["xla"], rtol=2e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# norm+activation join (compute tier): scale_bias_relu + BatchNormReLU
# ---------------------------------------------------------------------------
def test_scale_bias_relu_matches_xla(rng):
    from horovod_tpu.ops.elementwise import scale_bias_relu

    x = jnp.asarray(rng.normal(size=(2, 4, 4, 128)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(scale_bias_relu(x, s, b)),
        np.asarray(jax.nn.relu(x * s + b)), rtol=1e-6, atol=1e-6)


def test_scale_bias_relu_gradients(rng):
    from horovod_tpu.ops.elementwise import scale_bias_relu

    x = jnp.asarray(rng.normal(size=(2, 4, 4, 128)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    gp = jax.grad(lambda x, s, b: jnp.sum(scale_bias_relu(x, s, b) ** 2),
                  argnums=(0, 1, 2))(x, s, b)
    gx = jax.grad(lambda x, s, b: jnp.sum(jax.nn.relu(x * s + b) ** 2),
                  argnums=(0, 1, 2))(x, s, b)
    for a, c in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


def test_batchnorm_relu_module_matches_flax(rng):
    """BatchNormReLU (the norm_act='pallas' module) == BatchNorm+relu:
    outputs, updated running stats, parameter grads, and input grads
    (the full BN backward through batch mean/var), train AND eval."""
    import flax.linen as nn

    from horovod_tpu.models.resnet import BatchNormReLU

    class Ref(nn.Module):
        train: bool

        @nn.compact
        def __call__(self, x):
            return nn.relu(nn.BatchNorm(
                use_running_average=not self.train, momentum=0.9,
                epsilon=1e-5, dtype=jnp.float32)(x))

    x = jnp.asarray(rng.normal(size=(8, 6, 6, 32)), jnp.float32)
    ref = Ref(train=True)
    vref = ref.init(jax.random.PRNGKey(0), x)
    fused = BatchNormReLU(use_running_average=False, dtype=jnp.float32)
    vf = fused.init(jax.random.PRNGKey(0), x)
    oref, mref = ref.apply(vref, x, mutable=["batch_stats"])
    of, mf = fused.apply(vf, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(of), np.asarray(oref),
                               rtol=1e-5, atol=1e-5)
    bs_r = mref["batch_stats"]["BatchNorm_0"]
    np.testing.assert_allclose(np.asarray(mf["batch_stats"]["mean"]),
                               np.asarray(bs_r["mean"]), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(mf["batch_stats"]["var"]),
                               np.asarray(bs_r["var"]), rtol=1e-4,
                               atol=1e-6)

    gxf = jax.grad(lambda x: jnp.sum(
        fused.apply(vf, x, mutable=["batch_stats"])[0] ** 2))(x)
    gxr = jax.grad(lambda x: jnp.sum(
        ref.apply(vref, x, mutable=["batch_stats"])[0] ** 2))(x)
    np.testing.assert_allclose(np.asarray(gxf), np.asarray(gxr),
                               rtol=1e-4, atol=1e-3)

    ev_f = BatchNormReLU(use_running_average=True, dtype=jnp.float32)
    ev_r = Ref(train=False)
    np.testing.assert_allclose(np.asarray(ev_f.apply(vf, x)),
                               np.asarray(ev_r.apply(vref, x)),
                               rtol=1e-5, atol=1e-5)


def test_resnet_norm_act_pallas_trains(rng):
    """ResNet18(norm_act='pallas') initializes and runs a train-mode
    forward with finite output and the fused modules' batch stats in
    the mutable collection."""
    from horovod_tpu.models.resnet import ResNet18

    model = ResNet18(num_classes=10, dtype=jnp.float32,
                     norm_act="pallas")
    x = jnp.asarray(rng.uniform(size=(2, 16, 16, 3)), jnp.float32)
    v = model.init(jax.random.PRNGKey(0), x, train=True)
    out, mutated = model.apply(v, x, train=True, mutable=["batch_stats"])
    assert out.shape == (2, 10)
    assert np.isfinite(np.asarray(out)).all()
    flat = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert flat, "fused BatchNormReLU must own running stats"
