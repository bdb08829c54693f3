"""DCGAN generator/discriminator (reference family: examples/dcgan/).

The reference trains a GAN elastically by wrapping only the
discriminator in AdaptiveDataParallel (its gradient statistics drive
the adaptive machinery) while the generator trains alongside
(reference: examples/dcgan noted in SURVEY.md section 2.6). The same
shape here: wrap the discriminator loss in an ElasticTrainer and step
the generator with :func:`make_generator_step`.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax


class Generator(nn.Module):
    latent_dim: int = 64
    base_features: int = 64
    channels: int = 3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, z):
        conv_t = partial(
            nn.ConvTranspose, dtype=self.dtype, use_bias=False
        )
        norm = partial(nn.GroupNorm, num_groups=8, dtype=self.dtype)
        x = nn.Dense(4 * 4 * self.base_features * 4, dtype=self.dtype)(z)
        x = x.reshape((-1, 4, 4, self.base_features * 4))
        x = nn.relu(norm()(x))
        x = conv_t(self.base_features * 2, (4, 4), strides=(2, 2))(x)
        x = nn.relu(norm()(x))  # 8x8
        x = conv_t(self.base_features, (4, 4), strides=(2, 2))(x)
        x = nn.relu(norm()(x))  # 16x16
        x = conv_t(self.channels, (4, 4), strides=(2, 2))(x)  # 32x32
        return jnp.tanh(x.astype(jnp.float32))


class Discriminator(nn.Module):
    base_features: int = 64
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, images):
        conv = partial(
            nn.Conv, strides=(2, 2), dtype=self.dtype, use_bias=False
        )
        norm = partial(nn.GroupNorm, num_groups=8, dtype=self.dtype)
        x = images.astype(self.dtype)
        x = nn.leaky_relu(conv(self.base_features, (4, 4))(x), 0.2)
        x = nn.leaky_relu(
            norm()(conv(self.base_features * 2, (4, 4))(x)), 0.2
        )
        x = nn.leaky_relu(
            norm()(conv(self.base_features * 4, (4, 4))(x)), 0.2
        )
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(1, dtype=jnp.float32)(x)[..., 0]


def init_dcgan(rng=None, latent_dim=64, base_features=64, channels=3):
    rng = rng if rng is not None else jax.random.key(0)
    g_rng, d_rng = jax.random.split(rng)
    generator = Generator(
        latent_dim=latent_dim, base_features=base_features,
        channels=channels,
    )
    discriminator = Discriminator(base_features=base_features)
    g_params = generator.init(g_rng, jnp.zeros((1, latent_dim)))["params"]
    d_params = discriminator.init(
        d_rng, jnp.zeros((1, 32, 32, channels))
    )["params"]
    return generator, g_params, discriminator, d_params


def discriminator_loss_fn(discriminator, generator):
    """ElasticTrainer loss for the discriminator (construct the
    trainer with ``has_aux=True``): the batch carries real images and
    latent noise, and the current generator params arrive through the
    replicated ``aux`` input so alternating G/D updates never
    recompile."""

    def loss_fn(d_params, batch, rng, g_params):
        fakes = generator.apply({"params": g_params}, batch["z"])
        real_logits = discriminator.apply(
            {"params": d_params}, batch["image"]
        )
        fake_logits = discriminator.apply({"params": d_params}, fakes)
        real_loss = optax.sigmoid_binary_cross_entropy(
            real_logits, jnp.ones_like(real_logits)
        ).mean()
        fake_loss = optax.sigmoid_binary_cross_entropy(
            fake_logits, jnp.zeros_like(fake_logits)
        ).mean()
        return real_loss + fake_loss

    return loss_fn


def make_generator_step(generator, discriminator, optimizer, mesh=None):
    """Jitted generator update (not elastic-wrapped, mirroring the
    reference's one-wrapped-model GAN recipe).

    Pass the discriminator trainer's ``mesh`` for any multi-device or
    multi-process run: ``z`` is then consumed data-sharded and the
    generator gradient is ``pmean``'d over the data axis, so every
    replica applies the identical update — without it, per-process
    loader shards would silently diverge the generator params across
    an elastic allocation (rank 0's copy then wins at checkpoint
    time). ``mesh=None`` keeps the single-device fast path."""

    def loss_of(gp, d_params, z):
        fakes = generator.apply({"params": gp}, z)
        logits = discriminator.apply({"params": d_params}, fakes)
        return optax.sigmoid_binary_cross_entropy(
            logits, jnp.ones_like(logits)
        ).mean()

    if mesh is None:

        @jax.jit
        def step(g_params, g_opt_state, d_params, z):
            loss, grads = jax.value_and_grad(loss_of)(
                g_params, d_params, z
            )
            updates, g_opt_state = optimizer.update(
                grads, g_opt_state, g_params
            )
            return (
                optax.apply_updates(g_params, updates),
                g_opt_state,
                loss,
            )

        return step

    from jax.sharding import PartitionSpec as P

    from adaptdl_tpu.parallel.mesh import DATA_AXIS

    def per_replica(g_params, g_opt_state, d_params, z_local):
        g_v = jax.lax.pcast(g_params, DATA_AXIS, to="varying")
        loss, grads = jax.value_and_grad(loss_of)(
            g_v, d_params, z_local
        )
        grads = jax.lax.pmean(grads, DATA_AXIS)
        loss = jax.lax.pmean(loss, DATA_AXIS)
        updates, g_opt_state = optimizer.update(
            grads, g_opt_state, g_params
        )
        return (
            optax.apply_updates(g_params, updates),
            g_opt_state,
            loss,
        )

    return jax.jit(
        jax.shard_map(
            per_replica,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(DATA_AXIS)),
            out_specs=(P(), P(), P()),
        )
    )
