"""Same-chip plain-JAX/Flax ResNet-50 training baseline.

This is the honest yardstick for BASELINE.json's north-star target
("images/sec/chip >= 70% of reference JAX/Flax"): an idiomatic
flax.linen ResNet-50 (v1, bottleneck) with an optax SGD-momentum train
step, jitted with donated buffers — i.e. what a competent JAX user
would write from scratch, with none of this repo's machinery.
`bench.py --phase jax_baseline` times it on the same chip as the
framework's fused step and reports the ratio as `vs_jax_flax`.

The model layout matches the reference's `example/image-classification/
symbols/resnet.py` (ResNet-50 = units [3,4,6,3], bottleneck) so both
sides run the same FLOPs.
"""
import functools

import jax
import jax.numpy as jnp


def _conv(ch, kernel, strides, dtype, name):
    import flax.linen as nn
    return nn.Conv(ch, kernel, strides=strides, padding=[(k // 2, k // 2) for k in kernel],
                   use_bias=False, dtype=dtype, name=name)


def make_model(num_classes=1000, compute_dtype=None):
    """Build a flax.linen ResNet-50. compute_dtype=jnp.bfloat16 runs
    conv/matmul in bf16 with fp32 params (mixed-precision policy)."""
    import flax.linen as nn
    dtype = compute_dtype or jnp.float32

    class BottleneckBlock(nn.Module):
        ch: int
        strides: tuple
        project: bool

        @nn.compact
        def __call__(self, x, train):
            norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                     momentum=0.9, epsilon=2e-5, dtype=dtype)
            residual = x
            y = _conv(self.ch, (1, 1), (1, 1), dtype, "conv1")(x)
            y = norm(name="bn1")(y)
            y = nn.relu(y)
            y = _conv(self.ch, (3, 3), self.strides, dtype, "conv2")(y)
            y = norm(name="bn2")(y)
            y = nn.relu(y)
            y = _conv(self.ch * 4, (1, 1), (1, 1), dtype, "conv3")(y)
            y = norm(name="bn3")(y)
            if self.project:
                residual = _conv(self.ch * 4, (1, 1), self.strides, dtype, "proj")(x)
                residual = norm(name="bn_proj")(residual)
            return nn.relu(y + residual)

    class ResNet50(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.astype(dtype)
            x = _conv(64, (7, 7), (2, 2), dtype, "conv0")(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=2e-5, dtype=dtype, name="bn0")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
            for stage, (n_units, ch) in enumerate(
                    zip((3, 4, 6, 3), (64, 128, 256, 512))):
                for unit in range(n_units):
                    strides = (2, 2) if unit == 0 and stage > 0 else (1, 1)
                    x = BottleneckBlock(ch, strides, project=(unit == 0))(x, train)
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(num_classes, dtype=jnp.float32, name="fc")(x)
            return x

    return ResNet50()


def make_train_step(model, lr=0.05, momentum=0.9):
    """One jitted fwd+bwd+SGD step with donated params/opt-state —
    the plain-JAX analog of the framework's fused tpu_sync step."""
    import optax
    tx = optax.sgd(lr, momentum=momentum)

    def loss_fn(params, batch_stats, images, labels):
        logits, mut = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        return loss, mut["batch_stats"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, batch_stats, opt_state, images, labels):
        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, batch_stats, opt_state, loss

    return tx, step


def bench(batch=32, n_iter=15, compute_dtype=None, image_size=224, seed=0):
    """Returns images/sec for the flax train step (NHWC input, the
    layout XLA prefers on TPU; the framework feeds NCHW and transposes,
    which XLA folds into the first conv either way)."""
    import time
    import numpy as np
    model = make_model(compute_dtype=compute_dtype)
    rng = np.random.RandomState(seed)
    images0 = jnp.asarray(rng.uniform(-1, 1, (batch, image_size, image_size, 3)),
                          dtype=jnp.float32)
    variables = jax.jit(lambda x: model.init(
        {"params": jax.random.PRNGKey(0)}, x, train=False))(images0)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx, step = make_train_step(model)
    opt_state = tx.init(params)
    # distinct pre-staged batches: per-step h2d copies would time the
    # link, not the step
    batches = []
    for _ in range(4):
        batches.append((
            jax.device_put(jnp.asarray(
                rng.uniform(-1, 1, (batch, image_size, image_size, 3)),
                dtype=jnp.float32)),
            jax.device_put(jnp.asarray(
                rng.randint(0, 1000, (batch,)), dtype=jnp.int32))))
    jax.block_until_ready(batches)
    for _ in range(2):  # compile + steady state
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, *batches[0])
    jax.block_until_ready(loss)
    tic = time.time()
    for i in range(n_iter):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, *batches[i % len(batches)])
    jax.block_until_ready(loss)
    return batch * n_iter / (time.time() - tic)
