"""A ring torus (major radius 1, minor 0.35), genus 1: the reference's
copy of the program's ``repro.core.gson.sampling.sample_torus``,
uniform in area by rejection on the minor angle from four times as many
draws; the genus-1 surface of arXiv:1503.08294's reconstruction
benchmarks."""
import jax
import jax.numpy as jnp

GENUS = 1


def sample(rng, n, big_r=1.0, small_r=0.35):
    k_theta, k_phi, k_rej = jax.random.split(rng, 3)
    theta = jax.random.uniform(k_theta, (n,), minval=0.0, maxval=2 * jnp.pi)
    m = 4 * n
    phi = jax.random.uniform(k_phi, (m,), minval=0.0, maxval=2 * jnp.pi)
    u = jax.random.uniform(k_rej, (m,))
    accept = u < (big_r + small_r * jnp.cos(phi)) / (big_r + small_r)
    phi = phi[jnp.argsort(~accept, stable=True)[:n]]
    x = (big_r + small_r * jnp.cos(phi)) * jnp.cos(theta)
    y = (big_r + small_r * jnp.cos(phi)) * jnp.sin(theta)
    z = small_r * jnp.sin(phi)
    return jnp.stack([x, y, z], axis=1).astype(jnp.float32)
